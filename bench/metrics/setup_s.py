"""setup_s (s, host clock): process start to the window's start — TPU
initialisation, the program's set-up, and the warm-up request with its
compile (served from the persistent cache after a checkout's first run)."""


def read(run):
    return run.setup_s
