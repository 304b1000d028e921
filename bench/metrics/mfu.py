"""Model step, whole run: model FLOPs of the work that served the
tokens surfaced in the traced slice (blocks, attention over the live
context, the LM head once per token; prompt chunks as
``work.served_spans`` finds them) over the slice's length times the
chip's bf16 peak, in %."""
from harness import work


def read(ctx):
    t = ctx.trace
    if not t or ctx.peak is None or t["window_s"] <= 0 \
            or ctx.traced is None:
        return None
    decode, prefill, firsts = work.served_spans(
        ctx.records, *ctx.traced, ctx.cell.engine["prefill_chunk"])
    if not decode and not prefill:
        return None
    f = work.model_flops(ctx.work, decode + prefill,
                         head_rows=len(decode) + firsts)
    return 100.0 * f / (t["window_s"] * ctx.peak["bf16_flops"])
