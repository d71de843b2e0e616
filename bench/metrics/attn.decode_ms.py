"""Device time per call of the jitted decode step spent in operations
under the attention join point: the operations whose name stack (the
DSL's `jax.named_scope` path, e.g. `.../blocks0/.../block/attn/...`)
holds it, inside the window's `jit_decode_step` calls (bench/spans.py)."""

from bench import spans

MODULE = "jit_decode_step"


def read(run):
    trace = spans.for_run(run)
    if trace is None:
        return None
    calls, seconds = spans.scope_time(trace, spans.window(run), MODULE,
                                      spans.ATTENTION)
    if not calls or not seconds:
        return None
    return 1e3 * seconds / calls
