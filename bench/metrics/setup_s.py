"""setup_s: seconds from the process's start to the window's start
(loading, census cache, program state, compile or cache load, warm-up)."""


def read(obs):
    return obs["setup_s"]
