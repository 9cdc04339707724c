"""end to end: process start to the window's opening."""


def read(obs):
    return obs.get("setup_s")
