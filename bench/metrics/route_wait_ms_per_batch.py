"""route_wait_ms_per_batch (ms, program spans): per micro-batch, the
seconds of the program's ``ingest/route_wait`` span, the host blocked in
``TenantWindowIngest.push`` until the routed slots are back. The chip runs
executables in launch order, so this wait holds every update and rotation
queued ahead of the route, not the route alone."""


def read(run):
    q = run["qobs"]
    if not q or "ingest/route_wait" not in q or not run["batches"]:
        return None
    return q["ingest/route_wait"] / run["batches"] * 1e3
