"""compiles_in_window (count, program spans): the program's ``jax/compile``
events in the traced window, one per XLA compile or persistent-cache load.
Warm-up covers every shape the window uses, so it should read 0; each
compile is printed on stderr with the function and the span that caused
it. None where the program records no compiles."""

import harness
from repro.obs import trace as obs_trace


def read(run):
    if not run["qobs"] or not hasattr(obs_trace, "COMPILE_EVENT"):
        return None
    hits = [e for e in obs_trace.events() if e["name"] == obs_trace.COMPILE_EVENT]
    for e in hits:
        harness.log(f"compile in window: {e['args']['fun']} under {e['args']['path'] or '(no span)'}"
                    f" {e['dur'] / 1e3!r} ms")
    return len(hits)
