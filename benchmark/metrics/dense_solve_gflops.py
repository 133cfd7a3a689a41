"""(2/3 n^3 + 2 n^2) per completed dense step, over the window's seconds."""


def read(obs):
    if not obs.get("steps"):
        return None
    return obs["steps"] * obs["step_flops"] / obs["window_s"] / 1e9
