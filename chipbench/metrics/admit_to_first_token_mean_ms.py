"""Mean time (ms) from a request's admission to its first token sampled,
over the run: the engine's admit_to_first_token_s counter over its
first_tokens counter.  A program without these counters reports
nothing."""


def read(run):
    c0, c1 = run.counters0, run.counters1
    if "first_tokens" not in c1:
        return None
    n = c1["first_tokens"] - c0["first_tokens"]
    if not n:
        return None
    return 1e3 * (c1["admit_to_first_token_s"]
                  - c0["admit_to_first_token_s"]) / n
