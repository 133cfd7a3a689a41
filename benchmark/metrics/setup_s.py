"""Process start to the first timed request: loading, generating the
inputs, compiling or loading every program the window runs, warming."""


def read(obs):
    return obs.get("setup_s")
