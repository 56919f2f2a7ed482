"""route_ms_per_batch (ms, program spans): per micro-batch, the harness's
span around route + push less the program's qobs ``ingest/push`` span, i.e.
key-directory routing with its host sync of the slots."""


def read(run):
    q, spans = run["qobs"], run["spans"]
    if not q or "route+push" not in spans or not run["batches"]:
        return None
    return (spans["route+push"] - q.get("ingest/push", 0.0)) / run["batches"] * 1e3
