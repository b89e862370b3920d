"""Every output token of every serve() call started in the window, over
the time from the window's start to the end of the last such call."""


def read(run):
    if not run.calls:
        return None
    tokens = sum(len(r["served"]) for r in run.requests
                 if r["served"] is not None)
    return tokens / max(end for _, end, _ in run.calls)
