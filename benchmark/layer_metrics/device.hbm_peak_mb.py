"""Device layer: peak bytes in use on the fullest chip (INFO ``deviceN:
peak_bytes_in_use``) after the window, in MB."""


def read(obs):
    return obs.memory_peak_bytes / 1e6 if obs.memory_peak_bytes else None
