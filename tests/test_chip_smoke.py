"""chip_smoke.py rehearsed on the CPU at smoke width.

Its command line refuses any platform but a TPU, so these tests drive its
phase functions directly: the one-chip phases in this process (kernels in
interpret mode), the four-chip phase on four forced host devices."""
import importlib.util
import os
import shutil
import subprocess
import sys

from conftest import REPO, run_forced_mesh

from repro.configs import ShapeConfig, get_arch, smoke_config

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_at_smoke_width():
    cs = _load_smoke()
    lines = []
    cs.run_one_chip(
        smoke_config(get_arch(cs.ARCH)), ShapeConfig("t", 64, 2, "train"),
        steps=4, emit=lines.append, on_chip=False,
    )
    text = "\n".join(lines)
    for phase in ("a float", "b intsgd zero1", "c intsgd fused"):
        assert sum(ln.startswith(f"{phase} step ") for ln in lines) == 4, text
    assert "equal to ref True, pack kernel equal to ref True" in text
    assert text.count("compile ") == 4, text


def test_four_chip_phase_on_forced_host_devices():
    out = run_forced_mesh(
        f"""
import importlib.util
from repro.configs import ShapeConfig, get_arch, smoke_config
spec = importlib.util.spec_from_file_location("chip_smoke", {SMOKE!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.run_four_chips(smoke_config(get_arch(cs.ARCH)),
                  ShapeConfig("t", 64, 8, "train"), steps=4)
print("FOUR_CHIP_PHASE_PASSED")
"""
    )
    assert "FOUR_CHIP_PHASE_PASSED" in out
    assert "parameters replicated on all 4 devices: True" in out
    assert "split over them: True" in out


def test_gradient_reductions_reads_compiled_hlo():
    cs = _load_smoke()
    hlo = "\n".join([
        "  %a = f32[] all-reduce(f32[] %x), to_apply=%add",
        "  %b = (s32[1024]{0}, f32[4]{0}) all-reduce-start(s32[1024]{0} %w, "
        "f32[4]{0} %s), to_apply=%add",
        "  %c = (s32[1024]{0}, f32[4]{0}) all-reduce-done(%b)",
        "  %d = bf16[8,4096]{1,0} reduce-scatter(bf16[32,4096]{1,0} %g), "
        "dimensions={0}",
        "  %e = f32[4096]{0} all-gather(f32[1024]{0} %p), dimensions={0}",
    ])
    floats, ints = cs.gradient_reductions(hlo)
    assert floats == [("reduce-scatter", "bf16", 8 * 4096)]
    assert ints == [("all-reduce-start", "s32", 1024)]
    assert cs.collectives(hlo, "all-gather") == [("all-gather", "f32", 4096)]


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, path], capture_output=True, text=True, timeout=300,
        env=env, cwd=cwd,
    )


def test_refuses_to_run_without_a_tpu():
    r = _run_script(SMOKE, REPO)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_fails_without_the_rest_of_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    r = _run_script(str(lone), str(tmp_path))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
