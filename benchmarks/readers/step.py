"""Round programs (bcfl_tpu/fed/client_step.py): the whole step's share of
the chip's peak, over the operations the configuration REQUIRES."""


def mfu_pct(ctx):
    if ctx["platform"] != "tpu":
        return None
    return ctx["yardstick"].mfu_pct(ctx["tokens_per_s_per_chip"], ctx["sizes"],
                                    ctx["seq"], ctx["device_kind"], ctx["cell"])
