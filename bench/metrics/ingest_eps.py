"""ingest_eps (events/s, host clock): events pushed in a closed-loop window
divided by its seconds; the window ends when every micro-batch has
retired, so only finished work counts."""


def read(run):
    if run["mix"]["arrival"] != "closed":
        return None
    return run["events"] / run["window_s"]
