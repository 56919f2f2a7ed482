"""route_dispatch_ms_per_batch (ms, program spans): per micro-batch, the
seconds of the program's ``ingest/route`` span in
``TenantWindowIngest.push``: the tenant ids' transfer, the epoch scalar and
the dispatch of ``key_directory.route``, without waiting for its result."""


def read(run):
    q = run["qobs"]
    if not q or "ingest/route" not in q or not run["batches"]:
        return None
    return q["ingest/route"] / run["batches"] * 1e3
