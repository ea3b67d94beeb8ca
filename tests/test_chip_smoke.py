"""chip_smoke.py without a chip: it must fail where there is none, and its
control flow is rehearsed at a tiny size on the CPU (on-chip-measurement
guide, section 2.1-2.2), so that a later change finds a wrong path, argument
or sharding rule here and not on the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO_DIR, "chip_smoke.py")


def _run_smoke(cwd, env):
    r = subprocess.run([sys.executable, os.path.join(cwd, "chip_smoke.py")],
                       cwd=cwd, env=env, capture_output=True, text=True,
                       timeout=300)
    return r.returncode, r.stdout


def test_smoke_fails_where_jax_is_held_to_the_cpu():
    rc, out = _run_smoke(_REPO_DIR, {**os.environ, "JAX_PLATFORMS": "cpu"})
    assert rc != 0
    assert '"ok"' not in out


def test_smoke_fails_without_the_program(tmp_path):
    shutil.copy(_SMOKE, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    rc, out = _run_smoke(str(tmp_path), env)
    assert rc != 0
    assert '"ok"' not in out


_REHEARSAL = """
import sys
import chip_smoke
chips = int(sys.argv[1])
sizes = dict(
    chip_smoke.FULL, platform="cpu",
    model=dict(vocab_size=97, num_layers=2, num_heads=2, embed_dim=32),
    batch=4, seq=64, ce_block=32, flash_shape=(2, 64, 2, 16),
    page_size=8, max_prompt_len=32, max_new_tokens=16, max_batch=4,
    decode_steps=3, train_deadline_s=300, serve_deadline_s=300)
device = chip_smoke.run(sizes, chips)
assert device == {"platform": "cpu", "kind": "cpu", "count": chips}, device
"""


@pytest.mark.slow    # 25 s + 15 s of a tier-1 window the suite overruns
@pytest.mark.parametrize("chips", [1, 4])
def test_smoke_phases_rehearsed_on_cpu(tmp_path, chips):
    """The smoke's own phases at a size the CPU can run, in a cluster whose
    node advertises chips it does not have: virtual CPU devices stand in
    for them.  In a process of its own, as the smoke's driver is: it must
    end with no JAX backend initialised.  Run it before spending chip time
    on a change to chip_smoke.py."""
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO_DIR,
        "RT_NUM_TPU_CHIPS": str(chips),
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}",
        # a CPU program loaded back from the cache logs its target's
        # features as errors
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
    }
    r = subprocess.run([sys.executable, "-c", _REHEARSAL, str(chips)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    phases = [json.loads(line)["phase"] for line in r.stdout.splitlines()
              if line.startswith('{"phase"')]
    assert phases == (["numerics", "train", "serve"] if chips == 1
                      else ["mesh", "one_device", "mesh_check"])
