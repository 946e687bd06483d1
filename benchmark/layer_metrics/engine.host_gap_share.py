"""Share of the traced window in which the device was idle while the host
was inside one of the harness's spans (dispatch, sync, record.read)."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    under = sum(v for k, v in t["idle_by_span"].items() if k != "_no_span_")
    return 100.0 * under / t["window_s"]
