"""Share of the chip's bf16 peak that the long-document filter's required
FLOPs fill over the window (``flops.py`` over the AFMoE counts of
``reference/afmoe.py``: projections with the output gate, the attention
over the last window of positions in sliding layers and every position in
full ones, the dense MLP of the leading layers, and per MoE layer the
router, the routed experts a token chooses and the shared one)."""


def read(run):
    return run.mfu_percent()
