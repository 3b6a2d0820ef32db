"""Process start to window start: imports, weights from the seed, engine
and page pool, warm-up (compilation or compile-cache loads)."""


def read(run):
    return run.setup_s
