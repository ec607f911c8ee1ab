"""Join kernel dispatch: kernel shapes first compiled inside the window
(growth of ``event_join._cache_size()``)."""


def read(run):
    if "cache0" not in run:
        return None
    return run["cache1"] - run["cache0"]
