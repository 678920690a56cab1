"""Builds the program under test for ``vgg16bn-mod``: the repository's
``models/cnn.make_vgg`` at the sizes of ``vgg16bn-mod.json``."""
from __future__ import annotations

from repro.models.cnn import VggConfig, make_vgg


def build_model(sizes: dict, n_stat: int):
    """(init, loss_fn, taps) of the program at these sizes."""
    if sizes["convs_per_stage"] != 2 or sizes["fc_hidden_layers"] != 1:
        raise ValueError("make_vgg builds 2 convs per stage and one hidden "
                         "FC layer; the configuration asks for another")
    if tuple(sizes["pool"]) != (2, 1) or sizes["kernel"] != 3:
        raise ValueError("make_vgg pools 2x1 with 3x3 kernels")
    cfg = VggConfig(stages=tuple(sizes["stages"]),
                    n_classes=int(sizes["n_classes"]),
                    fc_hidden=int(sizes["fc_hidden"]), n_stat=int(n_stat),
                    pool=(2, 1), img=int(sizes["img"]))
    init, loss_fn, _, taps = make_vgg(cfg)
    return init, loss_fn, taps


def data_shape(sizes: dict) -> dict:
    """What the traffic generator needs to know of the model."""
    return {"img": sizes["img"], "channels": sizes["channels"],
            "classes": sizes["n_classes"]}


def n_tokens(data: dict) -> int:
    """Samples per step, over which the loss is a mean."""
    return int(data["batch"])


def tap_rows(sizes: dict, data: dict) -> dict:
    """Rows each tapped matmul multiplies per step: batch × output
    positions for a conv, the batch for an FC layer."""
    batch = int(data["batch"])
    rows = {}
    h, w = sizes["img"], sizes["img"]
    for s in range(len(sizes["stages"])):
        for j in range(sizes["convs_per_stage"]):
            rows[f"conv{s}_{j}"] = batch * h * w
        h //= sizes["pool"][0]
        w //= sizes["pool"][1]
    for j in range(sizes["fc_hidden_layers"] + 1):
        rows[f"fc{j}"] = batch
    return rows


def model_flops(sizes: dict, taps: dict, data: dict) -> float:
    """Forward and backward FLOPs of one step: every tapped matmul, as
    2·rows·d_in·d_out forward and twice that backward.  Batch norm,
    pooling and the loss are left out (well under a percent)."""
    rows = tap_rows(sizes, data)
    fwd = sum(2.0 * rows[n] * t.d_in * t.d_out for n, t in taps.items())
    return 3.0 * fwd


def tiny(cell):
    """The cell at widths a CPU test can hold, changed in place: two
    stages of 8 and 16 channels on 8x8 images, FC 256 -> 20 -> 10, r=8,
    n_stat=16, batch 4; every factor mode of the full cell still occurs
    (Brand, low-rank from M, dense), and each step kind of the cell runs
    among the checked steps."""
    cell.sizes.update(stages=[8, 16], fc_hidden=20, img=8)
    cell.job["data"].update(batch=4, pool=8)
    cell.job["optimizer"].update(r=8, n_stat=16, max_dense_dim=64)
    return cell
