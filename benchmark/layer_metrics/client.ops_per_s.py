"""Client layer: verified operations a second over the window.  In an open
loop below the knee it is the offered rate, so it is a layer metric there."""


def read(obs):
    return obs.ops_per_s or None
