"""Virtual-CPU-mesh pinning for tools, tests, and driver entry points.

Callers that must never touch real hardware (dryrun_multichip, CPU-only
tools) pin the CPU platform and a device count here, before jax's first
backend use.
"""

from __future__ import annotations

import os
import re


def virtual_host_env(devices_per_host: int) -> dict[str, str]:
    """Env vars that give a CHILD process a virtual CPU host with
    ``devices_per_host`` devices — the per-host half of a simulated pod
    (launch.py --num-processes N gives the other half). Used by the elastic
    soak (bench.py, tests/test_elastic_resume.py): N hosts x M fake devices
    re-form to a smaller N at the same M after a host loss.

    Unlike :func:`pin_virtual_cpu_mesh` this only RETURNS the env (for
    subprocess spawning); the child's own jax init applies it.
    """
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={int(devices_per_host)}",
    }


def ring_neighbors(host: int, ring_size: int) -> tuple[int, int]:
    """The two ICI-ring neighbors of ``host`` on a ``ring_size`` host ring.
    The simulated pod's inter-host topology is a single ring (the wraparound
    torus axis hosts sit on): host h links to (h-1) % N and (h+1) % N."""
    n = int(ring_size)
    h = int(host) % n
    return ((h - 1) % n, (h + 1) % n)


def is_contiguous_arc(hosts: list[int], ring_size: int) -> bool:
    """True iff ``hosts`` occupy one unbroken arc of the ring — every
    consecutive pair of survivors is ICI-adjacent, so ring collectives run
    at full link bandwidth instead of hopping over evicted hosts."""
    n = int(ring_size)
    members = sorted(set(int(h) % n for h in hosts))
    if len(members) <= 1 or len(members) == n:
        return bool(members)
    in_arc = set(members)
    # An arc of k hosts has exactly k-1 adjacent pairs along the ring,
    # equivalently exactly one "gap edge" endpoint pair. Walk from any
    # member forward until leaving the set; if we collected everyone, the
    # set is one arc.
    start = members[0]
    # Find an arc start: a member whose predecessor is NOT a member.
    for h in members:
        if (h - 1) % n not in in_arc:
            start = h
            break
    seen = 0
    h = start
    while h in in_arc and seen < len(members):
        seen += 1
        h = (h + 1) % n
    return seen == len(members)


def select_survivors(candidates: list[int], k: int,
                     ring_size: int) -> tuple[list[int], list[int]]:
    """Deterministic topology-aware shrink: from the live ``candidates``
    (original host ids on a ``ring_size`` ICI ring), keep the ``k`` hosts
    forming the most ring-contiguous subset. Scans every length-``k`` arc of
    the ring and keeps the one covering the most candidates (smallest start
    offset wins ties → fully deterministic); shortfall is filled from the
    remaining candidates walking the ring forward from the arc. Returns
    ``(survivors, rejected)``, both sorted ascending.

    With every candidate alive this always yields a contiguous arc; after
    scattered losses it yields the least-bisected subset reachable.
    """
    n = int(ring_size)
    alive = sorted(set(int(h) % n for h in candidates))
    k = int(k)
    if k >= len(alive):
        return alive, []
    if k <= 0:
        return [], alive
    alive_set = set(alive)
    best_start, best_score = 0, -1
    for start in range(n):
        score = sum(1 for i in range(k) if (start + i) % n in alive_set)
        if score > best_score:
            best_start, best_score = start, score
    chosen = [(best_start + i) % n for i in range(k)
              if (best_start + i) % n in alive_set]
    # Fill any shortfall by walking forward from the arc's end — keeps the
    # patched-in hosts as close to the arc as the ring allows.
    offset = k
    while len(chosen) < k and offset < k + n:
        h = (best_start + offset) % n
        if h in alive_set and h not in chosen:
            chosen.append(h)
        offset += 1
    survivors = sorted(chosen)
    rejected = sorted(alive_set - set(survivors))
    return survivors, rejected


def pin_virtual_cpu_mesh(n_devices: int = 8) -> None:
    """Force an ``n_devices`` virtual-CPU platform before any backend init."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
