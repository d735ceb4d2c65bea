"""No fallback that hides the device (ISSUE 21).

``--backend tpu`` is the default of every CLI and means TPUs: on a process
whose devices are CPUs (this test process) it must raise, not train on the
CPU and exit 0. ``--backend cpu`` stays the explicit way to run on the CPU,
and a Pallas op picks Mosaic or interpret mode from the platform it is lowered
for, in one place.
"""

import jax
import jax.numpy as jnp
import pytest

from distributeddeeplearning_tpu.config import ParallelConfig, TrainConfig
from distributeddeeplearning_tpu.parallel import mesh as meshlib


def _train_cli(argv):
    import train as train_cli
    return train_cli.main(argv)


def _generate_cli(argv):
    import generate as generate_cli
    return generate_cli.main(argv)


@pytest.mark.core
@pytest.mark.parametrize("run", [
    pytest.param(lambda: meshlib.backend_devices("tpu"),
                 id="backend_devices"),
    pytest.param(lambda: meshlib.make_mesh(ParallelConfig(), backend="tpu"),
                 id="make_mesh"),
    pytest.param(lambda: _train_cli(
        ["--model", "resnet18_thin", "--synthetic", "--steps", "1",
         "--no-compile-cache"]), id="train.py-default-backend"),
    pytest.param(lambda: _generate_cli(
        ["--model", "gpt_tiny", "--checkpoint-dir", "/nonexistent",
         "--prompt-ids", "1,2,3", "--max-new-tokens", "2"]),
        id="generate.py-default-backend"),
])
def test_backend_tpu_raises_on_a_cpu_only_process(run):
    with pytest.raises(RuntimeError, match="backend 'tpu' was asked for"):
        run()


@pytest.mark.core
def test_backend_cpu_is_the_explicit_way_and_none_is_ambient(devices8):
    assert meshlib.backend_devices("cpu") == jax.devices("cpu")
    assert meshlib.backend_devices(None) == jax.devices()
    mesh = meshlib.make_mesh(ParallelConfig(data=8), backend="cpu")
    assert mesh.devices.flat[0].platform == "cpu" and mesh.size == 8
    # the library default places on jax's devices as the process was started
    assert TrainConfig().backend is None
    with pytest.raises(ValueError, match="unknown backend"):
        meshlib.backend_devices("gpu")


def test_cli_backend_default_is_tpu():
    import generate as generate_cli  # noqa: F401 - importable without jax init
    import train as train_cli

    assert train_cli.parse_args([]).backend == "tpu"
    assert train_cli.build_config(train_cli.parse_args([])).backend == "tpu"
    assert train_cli.build_config(
        train_cli.parse_args(["--backend", "cpu"])).backend == "cpu"


def test_pallas_ops_interpret_off_tpu_without_asking_the_default_backend(
        monkeypatch):
    """ops/pallas.py decides at lowering time from the lowering platform:
    lowered for CPU devices the kernel is interpreted (no Mosaic custom
    call in the program) even if ``jax.default_backend()`` were to claim a
    TPU — no kernel module consults it."""
    from distributeddeeplearning_tpu.ops import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.ones((1, 128, 2, 16), jnp.float32)
    lowered = jax.jit(lambda q: flash_attention(q, q, q, causal=True)
                      ).lower(q)
    assert "tpu_custom_call" not in lowered.as_text()
    out = lowered.compile()(q)
    assert out.shape == q.shape and bool(jnp.isfinite(out).all())
