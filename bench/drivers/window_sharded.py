"""Driver ``window_sharded``: the ``window`` driver's tenant ingest
(``sketchstream.ingest.TenantWindowIngest``) with its WindowArray
row-sharded over a mesh of the cell's chips (``launch.mesh.make_sketch_mesh``,
contiguous slot ranges over the ``"sketch"`` axis). Each sealed micro-batch
is placed replicated on every chip, each shard folds in the events of its
own rows, and the ring rotates shard-locally behind the retire barrier. The
key directory stays on the first device. Closed-loop mixes only: sharded
window reads are not driven.

Set-up warms every shape the window uses, as the ``window`` driver does:
one micro-batch of zero-weight events (dropped by the update's live-weight
mask) and one rotation of the still empty ring. The sampled rows are read
back from the sharded planes with ``jnp.take`` (the ``window`` driver's
``Run``).
"""

from __future__ import annotations

import pathlib

import numpy as np

import harness
import loop
from repro.core import SketchConfig, key_directory
from repro.launch.mesh import make_sketch_mesh
from repro.sketchstream import ingest

window = harness.load_module(pathlib.Path(__file__).with_name("window.py"), "driver")


def run(ctx):
    conf, mix, st = ctx.cell.config, ctx.cell.mix, ctx.stream
    if mix["arrival"] != "closed":
        raise SystemExit("bench: the window_sharded driver drives closed-loop mixes only")
    cfg = SketchConfig(m=conf["m"], b=conf["b"], seed=conf["sketch_seed"])
    dcfg = key_directory.DirectoryConfig(capacity=conf["k"], seed=conf["directory_seed"])
    icfg = ingest.IngestConfig(batch_size=conf["batch"], queue_depth=conf["queue_depth"])
    tw = ingest.TenantWindowIngest(cfg, dcfg, conf["epochs"], icfg,
                                   mesh=make_sketch_mesh(ctx.cell.chips))
    ctx.mark("state")
    batch, chunk = conf["batch"], st.chunk_len
    zero = np.zeros(chunk, np.float32)
    for c in range(batch // chunk):
        ch = st.chunk(c)
        tw.push((ch.t_lo, ch.t_hi), ch.ids, zero)
    tw.rotate()
    tw.result()

    def push(ch):
        tw.push((ch.t_lo, ch.t_hi), ch.ids, ch.w)

    w = loop.drive(ctx, push, batch=batch, settle=tw.result, flush=tw.pipe.flush,
                   rotate=tw.rotate)
    return window.Run(tw, w)
