"""Device time in the operations whose name starts with one of
``prefixes`` over the time in module events (chip 0), in %."""


def read(obs, *, prefixes):
    if obs.trace is None or not obs.trace["module_s"]:
        return None
    hit = sum(
        secs for name, secs in obs.trace["op_seconds"].items()
        if name.startswith(tuple(prefixes))
    )
    return 100.0 * hit / obs.trace["module_s"]
