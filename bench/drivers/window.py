"""Driver ``window``: sparse 64-bit tenant ids pushed through
``sketchstream.ingest.TenantWindowIngest`` (key-directory routing, donated
WindowArray micro-batch updates, rotation behind the retire barrier).

Open-loop mixes also read the fleet: the anytime full-ring read
(``window_array.estimate_ring_anytime``, to the host) and the sub-ring
read of ``subring_w`` epochs (``ops.window_union_estimate_op``, to the
host). Set-up warms every shape the window uses: one micro-batch of
zero-weight events (dropped by the update's live-weight mask, so the
state does not change), one rotation of the still empty ring, and each
read once.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import loop
from repro.core import SketchConfig, key_directory, window_array
from repro.kernels import ops
from repro.sketchstream import ingest


class Reads:
    def __init__(self, cfg, tw, w_sub: int):
        self.tw = tw
        sub = functools.partial(ops.window_union_estimate_op, cfg, w=w_sub)

        def window_subring_read(state):
            return sub(state)

        self._sub = jax.jit(window_subring_read)

    def anytime(self) -> np.ndarray:
        return np.asarray(window_array.estimate_ring_anytime(self.tw.pipe.state))

    def subring(self):
        state = self.tw.pipe.state
        jax.block_until_ready(state)
        t = time.perf_counter()
        out = np.asarray(self._sub(state))
        return out, time.perf_counter() - t


class Run:
    """The system after its window: the record, and the sampled rows."""

    def __init__(self, tw, window):
        self.tw, self.window = tw, window

    def rows(self, sample: np.ndarray) -> dict:
        st, idx = self.tw.result(), jnp.asarray(sample)
        take = lambda x, ax: np.asarray(jnp.take(x, idx, axis=ax))
        d = self.tw.directory
        return {
            "regs": take(st.regs, 1), "hists": take(st.hists, 1), "chats": take(st.chats, 1),
            "union_regs": take(st.union_regs, 0), "union_hists": take(st.union_hists, 0),
            "union_chats": take(st.union_chats, 0),
            "fingerprints": take(d.fingerprints, 0),
            "scalars": np.array([int(st.head), int(st.filled), int(st.epoch_id), int(d.n_routed)]),
            "reads": {k: v[sample] for k, v in self.window.last.items()},
        }

    def close(self) -> None:
        self.tw = None


def build(conf: dict):
    cfg = SketchConfig(m=conf["m"], b=conf["b"], seed=conf["sketch_seed"])
    dcfg = key_directory.DirectoryConfig(capacity=conf["k"], seed=conf["directory_seed"])
    icfg = ingest.IngestConfig(batch_size=conf["batch"], queue_depth=conf["queue_depth"])
    return cfg, ingest.TenantWindowIngest(cfg, dcfg, conf["epochs"], icfg)


def run(ctx) -> Run:
    conf, mix, st = ctx.cell.config, ctx.cell.mix, ctx.stream
    cfg, tw = build(conf)
    ctx.mark("state")
    batch, chunk = conf["batch"], st.chunk_len
    zero = np.zeros(chunk, np.float32)
    for c in range(batch // chunk):
        ch = st.chunk(c)
        tw.push((ch.t_lo, ch.t_hi), ch.ids, zero)
    tw.rotate()
    reads = None
    if mix["arrival"] == "open":
        reads = Reads(cfg, tw, int(mix["subring_w"]))
        reads.anytime()
        reads.subring()
    tw.result()

    def push(ch):
        tw.push((ch.t_lo, ch.t_hi), ch.ids, ch.w)

    w = loop.drive(ctx, push, batch=batch, settle=tw.result, flush=tw.pipe.flush,
                   rotate=tw.rotate, reads=reads)
    return Run(tw, w)
