"""At the tiny size, the VGG cell's pool of batches and its plain
reference's record are bit for bit those kept in
``fixtures/vgg16bn-mod.bkfac.tiny.parity.json``, recorded before batch
kinds, layer-stacked taps and the per-configuration tiny size came in.

    python bench/tests/test_bench_parity.py

writes that fixture anew from the commit it runs on.
"""
import hashlib
import json
import pathlib

import jax
import numpy as np

import tiny  # noqa: I001 (first: it puts bench/ and src/ on the path)
import data as data_lib
import manifest

NAME = "vgg16bn-mod.bkfac"
SEED = 5000000011               # above 2**32: both halves of seed_key
FIXTURE = (pathlib.Path(__file__).resolve().parent / "fixtures"
           / f"{NAME}.tiny.parity.json")


def _digest(x) -> str:
    a = np.asarray(x)
    sha = hashlib.sha256(a.tobytes()).hexdigest()
    return f"{a.dtype}{list(a.shape)}:{sha}"


def record(name: str, seed: int) -> dict:
    """The pool's arrays by digest, and the reference's record of the
    checked steps, as ``bench/run.py`` makes them for this seed."""
    cell = tiny.tiny_cell(name)
    adapter = manifest.config_module(cell.config)
    params_key, data_key = jax.random.split(data_lib.seed_key(seed))
    data = dict(cell.job["data"], **adapter.data_shape(cell.sizes))
    pool = data_lib.make_pool(data, data_key)
    steps = int(cell.job["check_steps"])
    ref = manifest.reference_train().run(
        manifest.reference_module(cell.config), cell.sizes, cell.job,
        params_key, seed % (2 ** 31 - 1), pool[:steps],
        adapter.n_tokens(data))
    return {"workload": name, "seed": seed,
            "pool": [[_digest(x) for x in jax.tree_util.tree_leaves(b)]
                     for b in pool],
            "record": ref}


def test_pool_and_reference_record_are_unchanged():
    want = json.loads(FIXTURE.read_text())
    got = json.loads(json.dumps(record(want["workload"], want["seed"])))
    assert got["pool"] == want["pool"]
    assert got["record"] == want["record"]


if __name__ == "__main__":
    FIXTURE.write_text(json.dumps(record(NAME, SEED), indent=1,
                                  sort_keys=True) + "\n")
