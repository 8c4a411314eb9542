"""A percentile over the window's requests of an interval the program's
tracer recorded: between two marks (``received``, ``engine_queued``,
``first_token``, ``finished``) or the duration of a named span
(``queue_wait``, ``prefill``, ...). Requests are placed by ``received``."""

from chipbench.stats import percentile


def read(obs, *, q: float, marks=None, span=None):
    w0 = obs.window[0] + obs.unix_minus_mono
    w1 = obs.window[1] + obs.unix_minus_mono
    xs = []
    for tr in obs.traces:
        got = tr.get("marks", {})
        if not w0 <= got.get("received", -1.0) < w1:
            continue
        if marks is not None:
            a, b = marks
            if a in got and b in got:
                xs.append(1000.0 * (got[b] - got[a]))
        else:
            xs.extend(
                s["dur_ms"] for s in tr.get("spans", []) if s["name"] == span
            )
    return percentile(xs, q) if xs else None
