"""Mean time a detection sweep that started in the window waited in the
executor's queue, submit to start, in ms: the window's growth of the
executor's ``wait_seconds`` over its growth of ``started``
(``ctx.self_stats``, the window's growth of ``Session.self_stats()``)."""


def read(ctx):
    st = getattr(ctx, "self_stats", None)
    d = (st or {}).get("detect") or {}
    if not d.get("started"):
        return None
    return 1e3 * d["wait_seconds"] / d["started"]
