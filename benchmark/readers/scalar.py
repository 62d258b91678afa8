"""A number the run recorded under obs[<name>]."""


def read(obs, name, scale=1.0):
    v = obs.get(name)
    return None if v is None else v * scale
