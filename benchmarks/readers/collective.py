"""Mesh and collectives (bcfl_tpu/core/mesh.py, parallel/gspmd.py): the
all-reduce, all-gather, reduce-scatter and collective-permute operations on
the first device, and the part of them that nothing else hides. A cell on
one chip has none, and reports neither."""


def _coll(ctx, key):
    tr = ctx["trace"]
    if not tr or not tr["rounds"]:
        return None
    dev = tr["devices"][tr["first_device"]]
    if dev["collective_s"] <= 0:
        return None
    return 1e3 * dev[key] / tr["rounds"]


def ms_per_round(ctx):
    return _coll(ctx, "collective_s")


def exposed_ms_per_round(ctx):
    return _coll(ctx, "collective_exposed_s")
