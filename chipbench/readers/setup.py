"""Process start to the opening of the window: loading, compiling or
reading the compile cache, warmup, ``/health`` 200 and the ramp."""


def read(obs):
    return obs.setup_s
