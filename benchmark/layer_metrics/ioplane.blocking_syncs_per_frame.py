"""I/O plane: fetches that had to wait for device work (``blocking`` set on
the ``readback`` span) per frame of the slice."""


def read(obs):
    if not obs.frames:
        return None
    n = sum(int(s["attrs"].get("blocking", 0)) for f in obs.frames
            for s in f["spans"] if s["name"] == "readback")
    return n / len(obs.frames)
