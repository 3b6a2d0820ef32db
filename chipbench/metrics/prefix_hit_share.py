"""Share (%) of the prompt tokens of the requests due in the window that
were served from shared pages: the engine's pages_shared counter over the
run, times the page size, over those requests' prompt tokens."""


def read(run):
    prompt = sum(len(it.prompt) for it in run.in_window())
    pages = run.counters1["pages_shared"] - run.counters0["pages_shared"]
    if not prompt or not run.page_size or not run.mix.get("shared_prefix"):
        return None
    return 100.0 * pages * run.page_size / prompt
