"""Norms, decode: roofline time of the norms of the decode rows
surfaced in the traced slice (``work.norms``: each reads and writes its
rows once in bf16; rows as ``work.served_spans`` finds them) over the
device seconds of the ops under the ``norm`` scope inside
``_decode_h``, XLA norms or the AILayerNorm kernel alike, in %. None
where the trace has no scopes."""
from harness import scopes, work


def read(ctx):
    t = scopes.scope_time(ctx.trace, "decode", "norm")
    if not t or ctx.peak is None or ctx.traced is None:
        return None
    decode, _, _ = work.served_spans(ctx.records, *ctx.traced,
                                     ctx.cell.engine["prefill_chunk"])
    if not decode:
        return None
    f, b = work.norms(ctx.work, len(decode))
    return 100.0 * work.roofline_s(f, b, ctx.peak) / t
