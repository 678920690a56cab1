"""The plain reference draws each factor's sketch keys as the program
draws them: the step's key split over the program's factor buckets,
then over each bucket's slots, a stacked tap taking one slot per layer
(``core/kfac.py``, ``Kfac._bucketed_factor_work``)."""
import jax
import numpy as np
import pytest

import manifest
import program
import tiny


def _program_keys(opt, key) -> dict:
    out = {}
    for bkey, bucket in zip(jax.random.split(key, len(opt.factor_buckets)),
                            opt.factor_buckets):
        keys = jax.random.split(bkey, bucket.total)
        for e in bucket.entries:
            out[f"{e.name}/{e.side}"] = keys[e.offset:e.offset + e.count] \
                .reshape(e.stack + keys.shape[1:])
    return out


@pytest.mark.parametrize("name", tiny.NAMES + [tiny.TOY])
def test_sketch_keys_follow_the_program_bucket_slots(name, monkeypatch):
    cell = tiny.cell(name, monkeypatch)
    opt_spec = cell.job["optimizer"]
    _, _, taps = manifest.config_module(cell.config).build_model(
        cell.sizes, int(opt_spec["n_stat"]))
    key = jax.random.PRNGKey(3)
    want = _program_keys(program.make_optimizer(opt_spec, taps), key)
    got = manifest.reference_train().slot_keys(
        manifest.reference_module(cell.config).layers(cell.sizes), opt_spec,
        key)
    assert sorted(got) == sorted(want)
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(v),
                                      err_msg=k)
