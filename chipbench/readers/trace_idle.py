"""1 - (union of the device's operation intervals / traced window) on
chip 0, in %."""


def read(obs):
    if obs.trace is None or not obs.trace["window_s"]:
        return None
    return 100.0 * (1.0 - obs.trace["busy_s_chip0"] / obs.trace["window_s"])
