"""``shard_map`` as this codebase uses it: replication checking OFF.

The explicit-DP train path (train/steps.py) performs every cross-shard
reduction EXPLICITLY through parallel/collectives.py — the whole point of
the bucketed all-reduce is owning the grad-sync schedule — so the automatic
psum that replication-checked autodiff inserts for replicated inputs must be
off: per-shard values stay local until code psums them. Written for the one
installation the repo runs on (jax 0.9: ``jax.shard_map(check_vma=...)``).
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh=None, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``; ``mesh=None`` uses the
    ambient mesh (parallel/mesh.use_mesh).

    Callers own their collectives: gradients/metrics/statistics that must
    agree across shards are explicitly ``psum``/``pmean``-ed (train/steps.py,
    parallel/collectives.py), so no output relies on inferred replication.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
