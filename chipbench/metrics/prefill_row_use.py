"""Share (%) of the tokens of the run's chunk programs that carry a
prompt: the engine's prefill_live_tokens counter (live rows x chunk size)
over its prefill_row_tokens counter (rows x chunk size), summed over
every chunk program.  A program without these counters reports
nothing."""


def read(run):
    c0, c1 = run.counters0, run.counters1
    if "prefill_row_tokens" not in c1:
        return None
    rows = c1["prefill_row_tokens"] - c0["prefill_row_tokens"]
    if not rows:
        return None
    return 100.0 * (c1["prefill_live_tokens"]
                    - c0["prefill_live_tokens"]) / rows
