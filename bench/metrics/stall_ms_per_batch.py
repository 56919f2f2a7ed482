"""stall_ms_per_batch (ms, program spans): per micro-batch, the qobs
``ingest/stall`` (full queue) and ``ingest/retire`` (barrier) seconds."""


def read(run):
    q = run["qobs"]
    if not q or not run["batches"]:
        return None
    return (q.get("ingest/stall", 0.0) + q.get("ingest/retire", 0.0)) / run["batches"] * 1e3
