"""SPMD partitioner hygiene (VERDICT r4 Missing #5 / Next #3).

The dp4 x fsdp2 dryrun used to compile with XLA's "Involuntary full
rematerialization" warning: the embedding-table gradient scatter could not
bridge batch-sharded updates and an embed-over-fsdp output, so the
partitioner replicated the whole update activation. ops/embedding.py's
``embedding_lookup`` keeps the scatter on the supported
partial-scatter+allreduce path; the subprocess test here greps a real
compile's stderr so the bad path cannot silently return."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops.embedding import embedding_lookup


@pytest.mark.core
def test_embedding_lookup_matches_plain_gather():
    table = jax.random.normal(jax.random.key(0), (32, 8))
    ids = jax.random.randint(jax.random.key(1), (4, 6), 0, 32)

    def loss_new(t):
        return (embedding_lookup(t, ids) ** 2).sum()

    def loss_ref(t):
        return (t[ids] ** 2).sum()

    np.testing.assert_allclose(loss_new(table), loss_ref(table), rtol=1e-6)
    np.testing.assert_allclose(jax.grad(loss_new)(table),
                               jax.grad(loss_ref)(table), rtol=1e-6)


@pytest.mark.core
def test_embedding_lookup_bf16_table_grad_dtype():
    # The bwd casts back to the table dtype after the f32 scatter.
    table = jax.random.normal(jax.random.key(0), (16, 8), jnp.bfloat16)
    ids = jnp.array([[0, 3], [5, 0]])
    g = jax.grad(lambda t: embedding_lookup(t, ids).astype(jnp.float32)
                 .sum())(table)
    assert g.dtype == jnp.bfloat16


_REPRO = """
from distributeddeeplearning_tpu.hostmesh import pin_virtual_cpu_mesh
pin_virtual_cpu_mesh(8)
import json
from distributeddeeplearning_tpu.config import (
    DataConfig, OptimizerConfig, ParallelConfig, TrainConfig)
from distributeddeeplearning_tpu.train import loop
cfg = TrainConfig(
    model="bert_tiny", global_batch_size=16,
    dtype="float32", log_every=10**9,
    parallel=ParallelConfig(data=4, fsdp=2),
    data=DataConfig(dataset="mlm", seq_len=16, vocab_size=512),
    optimizer=OptimizerConfig(name="adamw", learning_rate=1e-4,
                              schedule="linear", label_smoothing=0.0))
print(json.dumps(loop.run(cfg, total_steps=1)))
"""


@pytest.mark.slow
def test_fsdp_compile_has_no_involuntary_rematerialization():
    """Compile+run the exact dp x fsdp config that used to warn, in a
    subprocess (XLA warnings go to the process stderr, not Python's), and
    assert the partitioner never fell back to replicate-then-repartition."""
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, "-c", _REPRO],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["final_step"] == 1
    assert "Involuntary full rematerialization" not in proc.stderr, (
        "the SPMD replicate-the-updates path is back:\n"
        + proc.stderr[-3000:])
