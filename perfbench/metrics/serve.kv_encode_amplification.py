"""KV pages the refresh re-encodes per page the model wrote, in the
traced wave. The engine counts both where the work happens and stamps
them on its spans: ``pages`` of each ``serve.kv_refresh`` (the whole
pool), against ``pages`` of each ``serve.prefill`` (its prompt pages) and
``active`` of each ``serve.decode`` (one page per active slot); the same
counts are ``SLOCounters.kv_pages_encoded`` and ``kv_pages_written``."""


def read(ctx):
    import programs
    p = programs.of(ctx)
    if not p:
        return None
    st = p.span_stats
    encoded = st.get("serve.kv_refresh", {}).get("pages")
    written = (st.get("serve.prefill", {}).get("pages", 0)
               + st.get("serve.decode", {}).get("active", 0))
    return encoded / written if encoded and written else None
