"""Share of the traced window in which the device was idle while the host
was inside the engine's ``fed.enqueue`` span (handing the round program to
the runtime; innermost span over the idle instant). The part of
``engine.host_gap_share`` that only the engine can shorten."""


def read(ctx):
    t = ctx["trace"]
    if not t or "fed.enqueue" not in t["idle_by_span"]:
        return None
    return 100.0 * t["idle_by_span"]["fed.enqueue"] / t["window_s"]
