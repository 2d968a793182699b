"""The harness drives a whole run with the timed path broken underneath
and ``correct`` comes out false, once for each fault the training cells
can have.  The look for a chip is skipped (``platform="cpu"``)."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from conftest import REPO, run_tiny


def _correct(root):
    rc, result, err = run_tiny(root, seed=11, seconds=0.3)
    assert rc == 0, err
    return result["correct"], result["checks"]


@pytest.fixture(scope="module")
def sound(tiny_root):
    """The run on the sound program that each fault is set against."""
    return _correct(tiny_root)


def _state_unchanged(monkeypatch):
    from repro.train import optimizer
    monkeypatch.setattr(optimizer, "adam_update",
                        lambda cfg, params, grads, state: (params, state, 0.0))


def _half_batch(monkeypatch):
    from repro.models import gcn as gcn_mod

    def loss(params, batch, use_kernel=False):
        logits = gcn_mod.gcn_forward(params, batch, use_kernel=use_kernel)
        half = logits.shape[0] // 2
        logp = jnp.log(jnp.exp(logits[:half]).sum(-1))
        picked = jnp.take_along_axis(logits[:half],
                                     batch.labels[:half, None], 1)[:, 0]
        return (logp - picked).mean()
    monkeypatch.setattr(gcn_mod, "gcn_loss", loss)


def _answer_altered(monkeypatch):
    from repro.core import generation
    real = generation._worker_generate

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        batch = out[0] if isinstance(out, tuple) else out
        batch = batch._replace(x_seed=batch.x_seed.at[0, 0].add(1.0))
        return (batch,) + out[1:] if isinstance(out, tuple) else batch
    monkeypatch.setattr(generation, "_worker_generate", altered)


@pytest.mark.parametrize("fault,caught_by", [
    (_state_unchanged, "update_gap"),
    (_half_batch, "grad_gap"),
    (_answer_altered, "bad_rows"),
])
def test_fault_makes_correct_false(tiny_root, sound, monkeypatch, fault,
                                   caught_by):
    ok, checks = sound
    assert ok, checks
    fault(monkeypatch)
    ok, checks = _correct(tiny_root)
    assert not ok
    assert checks[caught_by]["value"] > checks[caught_by]["limit"], checks


FOUR_WORKERS = r"""
import json, sys
sys.path[:0] = [{repo!r}, {src!r}, {tests!r}]
from pathlib import Path
from conftest import make_root, tiny_bench
from chipbench import harness
root = make_root(Path({tmp!r}))

def run():
    import io, contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = harness.main(["--workload", "tiny-w4", "--seed", "3",
                           "--seconds", "0.3"], root=root,
                          bench=tiny_bench(), platform="cpu",
                          data_dir=root / ".data")
    assert rc == 0
    return json.loads(buf.getvalue().splitlines()[-1])

sound = run()
from repro.core import generation
from jax import lax

class NoExchange:
    def __getattr__(self, name):
        return getattr(lax, name)

    @staticmethod
    def all_to_all(x, *args, **kwargs):
        return x

generation.lax = NoExchange()
broken = run()
print(json.dumps({{"sound": sound, "broken": broken}}))
"""


def test_exchange_left_out_makes_correct_false(tmp_path):
    """Four workers on four virtual CPU devices, with the all_to_all
    exchanges of the feature fetch replaced by the identity."""
    code = FOUR_WORKERS.format(repo=str(REPO), src=str(REPO / "src"),
                               tests=str(REPO / "tests" / "chipbench"),
                               tmp=str(tmp_path / "root"))
    # one compute thread, so the run takes no more than its own core
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               "--xla_cpu_multi_thread_eigen=false "
               "intra_op_parallelism_threads=1",
               JAX_ENABLE_COMPILATION_CACHE="false")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True, out["sound"]["checks"]
    assert out["sound"]["device"]["count"] == 4
    assert out["broken"]["correct"] is False
    assert out["broken"]["checks"]["bad_rows"]["value"] > 0
