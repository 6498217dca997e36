"""Host time of the monitor's probes on the step thread per training step
in the window, in ms: the window's growth of every probe's
``self_seconds`` (``Session.self_stats()``; the python probe's is a sampled
estimate of its profile hook) over the window's steps. Read from
``ctx.self_stats``, the window's growth of ``Session.self_stats()``; a
context without it, or a program without the counters, reads nothing."""


def read(ctx):
    st = getattr(ctx, "self_stats", None)
    if not st or not st.get("probes") or not ctx.steps:
        return None
    s = sum(sec for probes in st["probes"].values()
            for sec in probes.values())
    return 1e3 * s / ctx.steps
