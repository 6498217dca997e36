"""Mean run time of a detection sweep that completed in the window, in ms:
the window's growth of the executor's ``busy_seconds`` over its growth of
``completed`` (``ctx.self_stats``, the window's growth of
``Session.self_stats()``)."""


def read(ctx):
    st = getattr(ctx, "self_stats", None)
    d = (st or {}).get("detect") or {}
    if not d.get("completed"):
        return None
    return 1e3 * d["busy_seconds"] / d["completed"]
