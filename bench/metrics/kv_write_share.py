"""KV pool writes, decode: device seconds of the ops under the
``kv_write`` scope inside ``_decode_h`` (each layer's ``pk``/``pv``
write, its pool slice and ``.at[i].set``) over ``_decode_h``'s device
seconds, in %. None where the trace has no scopes."""
from harness import scopes, trace


def read(ctx):
    t, _ = trace.module_time(ctx.trace, "decode")
    w = scopes.scope_time(ctx.trace, "decode", "kv_write")
    if not t or not w:
        return None
    return 100.0 * w / t
