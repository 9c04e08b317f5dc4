"""Share of its roofline that the routed experts' grouped matmuls reach in
the traced ``_prefill_fn`` steps: the least time the chip could take,
max(FLOPs / peak FLOP/s, bytes / peak bytes/s), over the device time of
the step's grouped-matmul operations: the Mosaic calls XLA lowers
``jax.lax.ragged_dot`` to on the TPU (``%ragged-dot-metadata``,
``%ragged-dot-none.<n>``).

FLOPs: three d x expert_d_ff products for each (real token, expert) pair,
``Shape.expert_flops``: the traced steps' real tokens (their share of the
window's ``prefill_tokens``) times experts per token, per MoE layer.
Bytes: every expert's weights once per step and MoE layer,
``Shape.expert_bytes``.  At this cell's steps (8 rows of 32 tokens, 8
experts each: 2,048 pairs over 128 experts) every expert is hit in every
step, so each step reads every expert's weights at least once.  None where
the trace holds no such operations."""

STEP = "_prefill_fn"
GROUPED = r"^%ragged-dot[-\w.]* = "


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.op_seconds(GROUPED, STEP)
    steps_traced, _ = run.trace.module(STEP)
    steps = run.delta("backend", "prefill_steps")
    if not seconds or not steps_traced or not steps:
        return None
    s = run.shape
    tokens = run.delta("backend", "prefill_tokens") * steps_traced / steps
    f = s.expert_flops(tokens * s.top_k) * s.moe_layers
    b = s.expert_bytes() * s.moe_layers * steps_traced
    least = max(f / run.peaks["flops"], b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
