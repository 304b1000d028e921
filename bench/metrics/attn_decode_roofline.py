"""Paged E2Softmax kernel, decode: roofline time of the attention that
the tokens surfaced in the traced slice needed (each decode step reads
its lane's live context once per KV head) over the kernel's device
time inside ``_decode_h``, in %."""
from harness import trace, work


def read(ctx):
    t = trace.kernel_time(ctx.trace, "attention", "decode")
    if not t or ctx.peak is None or ctx.traced is None:
        return None
    decode, _, _ = work.served_spans(ctx.records, *ctx.traced,
                                     ctx.cell.engine["prefill_chunk"])
    if not decode:
        return None
    f, b = work.attention(ctx.work, decode)
    return 100.0 * work.roofline_s(f, b, ctx.peak) / t
