"""Helpers the metric readers share; not a metric (no entry names it).

The names below are the one place where the readers find the program's
kernels in a TPU trace (an op's name or custom-call target, matched as a
regular expression). The megakernel engine names its conv-stage launches
``megakernel_conv_stage``; every Pallas kernel lowers to a
``tpu_custom_call``. A kernel that replaces the conv stages under
another name leaves ``conv_stages_roofline`` silent: it brings a reader
of its own (``<kernel>_roofline``), and ``serve_mfu``, which needs no
name, still bounds the whole forward.
"""

CONV_STAGE = r"conv_stage"
PALLAS = r"tpu_custom_call"


def idle_share(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
