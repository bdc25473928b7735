import os
import subprocess
import sys

# smoke tests and benches must see exactly ONE device; only dryrun.py forces
# 512 placeholder devices (in its own process).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_forced_mesh(script: str, timeout=420):
    """Run `script` in a subprocess with 4 forced host devices, so the
    multi-device tests exercise real shard_map collectives while the parent
    process' single-device view stays untouched. Shared by
    test_distributed.py, test_runtime.py and test_overlap.py — ONE place to
    change the forced-mesh environment."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices, never an accelerator
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout
