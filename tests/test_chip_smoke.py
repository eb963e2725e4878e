"""``chip_smoke.py`` rehearsed on the CPU: its phases at smoke sizes.

The chip run loads 2^20 keys and serves llama3.2-3b at full width; here
the same phase functions run 2^10 keys and the smoke config, so a wrong
path, argument or check fails before any chip time is spent.  ``main()``
itself must refuse to run anywhere but a TPU.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import arena
from repro.models import get_config

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    # the script turns the device tier on in its own environment; keep
    # that out of the other tests this worker runs
    saved = os.environ.get("REPRO_DEVICE_TIER")
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    finally:
        if saved is None:
            os.environ.pop("REPRO_DEVICE_TIER", None)
        else:
            os.environ["REPRO_DEVICE_TIER"] = saved
    return mod


def _quiet(*_args):
    pass


def test_store_phase_reads_back_every_write(chip_smoke, monkeypatch):
    monkeypatch.setattr(arena, "_DEVICE_TIER_CACHE", True)
    out = chip_smoke.store_phase(0, 1 << 10, n_dag_calls=32, n_causal=8,
                                 chunk=256, log=_quiet)
    assert out["verified_merged"] == 1 << 10
    assert out["verified_replica_copies"] == 2 << 10
    assert out["verified_causal_writes"] == 8
    assert out["dag_writes"] > 0


# ``--chips 4`` rehearsed on four virtual CPU devices (jax fixes its
# device count at backend init, so it runs in a subprocess)
_SHARDED = """
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.sharded_store_phase(0, 1 << 10, n_dag_calls=32, n_causal=8, chunk=256)
"""


def test_sharded_store_phase_matches_single_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_DEVICE_TIER", None)
    proc = subprocess.run([sys.executable, "-c", _SHARDED], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PartitionSpec('kvs', None)" in proc.stdout
    assert "equals the single-device run bit for bit" in proc.stdout


def test_serve_phase_matches_reference(chip_smoke):
    out = chip_smoke.serve_phase(0, get_config("llama3.2-3b", smoke=True),
                                 new_tokens=8, bucket=32, log=_quiet)
    assert out["batched_invokes"] >= 1
    assert out["first_logits_max_abs_diff"] <= chip_smoke.LOGIT_TOL
    assert out["tokens_checked"] > 0
    assert out["engine_stats"]["tokens"] == 8 * 8


def test_main_refuses_a_non_tpu_platform(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out
