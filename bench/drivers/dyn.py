"""Driver ``dyn``: sparse 64-bit tenant ids routed by ``key_directory.route``
(slots synced to the host per arrival chunk), then pushed into
``sketchstream.ingest.dyn_pipeline`` on its default route (the jnp
plan/commit pair with the state donated). No rotation, no reads.

Set-up warms every shape the window uses: the route at the chunk size and
one micro-batch of zero-weight events, which the update's live-weight mask
drops, so the state does not change.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import loop
from repro.core import SketchConfig, dyn_array, key_directory
from repro.sketchstream import ingest


class Run:
    def __init__(self, pipe, directory, window):
        self.pipe, self.directory, self.window = pipe, directory, window

    def rows(self, sample: np.ndarray) -> dict:
        st, idx = self.pipe.result(), jnp.asarray(sample)
        take = lambda x: np.asarray(jnp.take(x, idx, axis=0))
        d = self.directory()
        return {"regs": take(st.regs), "hists": take(st.hists), "chats": take(st.chats),
                "fingerprints": take(d.fingerprints), "scalars": np.array([int(d.n_routed)])}

    def close(self) -> None:
        self.pipe = None


def run(ctx) -> Run:
    conf, st = ctx.cell.config, ctx.stream
    cfg = SketchConfig(m=conf["m"], b=conf["b"], seed=conf["sketch_seed"])
    dcfg = key_directory.DirectoryConfig(capacity=conf["k"], seed=conf["directory_seed"])
    icfg = ingest.IngestConfig(batch_size=conf["batch"], queue_depth=conf["queue_depth"])
    pipe = ingest.dyn_pipeline(cfg, dyn_array.init(cfg, conf["k"]), icfg)
    directory = key_directory.init(dcfg)
    ctx.mark("state")

    def push(ch, w=None):
        nonlocal directory
        slots, directory = key_directory.route(dcfg, directory, (ch.t_lo, ch.t_hi))
        pipe.push(np.asarray(slots), ch.ids, ch.w if w is None else w)

    zero = np.zeros(st.chunk_len, np.float32)
    for c in range(conf["batch"] // st.chunk_len):
        push(st.chunk(c), zero)
    pipe.result()
    w = loop.drive(ctx, push, batch=conf["batch"], settle=pipe.result, flush=pipe.flush)
    return Run(pipe, lambda: directory, w)
