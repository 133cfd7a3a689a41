"""Share of the traced window in which no op ran on the device."""


def read(obs):
    tr = obs.get("trace")
    return None if tr is None else 100.0 * tr.idle_share()
