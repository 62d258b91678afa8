"""A counter of the program or the harness, read as it stands."""


def read(obs, name, scale=1.0):
    v = obs.get("counters", {}).get(name)
    return None if v is None else v * scale
