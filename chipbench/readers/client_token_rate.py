"""Output tokens received in the window per second per chip."""


def read(obs):
    n = obs.tokens_in_window()
    return n / obs.seconds / obs.chips if n else None
