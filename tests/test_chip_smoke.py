"""chip_smoke.py's phase functions on the CPU at tiny-er size.

The script has no CPU mode: these tests steer its device check and its
fused-kernel assertion from here, shrink the served graph to tiny-er,
and check that the counts equal the oracle and the last line is the
JSON object the chip run prints.  Run as a program without a TPU, or
without the repo around it, the script must fail before any result.
"""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_device_check_refuses_cpu_and_accepts_tpu(monkeypatch):
    import jax

    with pytest.raises(SystemExit):
        chip_smoke.device_info(1)             # the CPU backend
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    assert chip_smoke.device_info(1) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(SystemExit):
        chip_smoke.device_info(4)


def test_phases_count_exactly_and_last_line_is_json(monkeypatch, capsys):
    import benchmarks.questions as questions

    kinds = []
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_info", lambda chips: device)
    # the CPU resolves the portable path; record what was asserted on
    monkeypatch.setattr(chip_smoke, "assert_fused",
                        lambda fp, hlo=None: kinds.append(fp))
    monkeypatch.setattr(chip_smoke, "SMALL_PATTERNS", ("triangle", "P1"))
    # one question from each of three categories
    picked = list({q.category: q for q in questions.inventory()}.values())
    monkeypatch.setattr(questions, "inventory", lambda: picked[:3])
    monkeypatch.setattr(chip_smoke, "SERVED_DATASET", "tiny-er")
    assert chip_smoke.main([]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": True, "device": device}
    # tiny-er: triangle + P1; tiny-labeled: three questions, counted cold
    # and again by a restarted engine that loads the executables
    assert any("[b] 8 counts equal the oracle" in line for line in out)
    assert any("[c] tiny-er house == oracle (27358)" in line for line in out)
    assert any("[c] tiny-er triangle == host count" in line for line in out)
    # P1 served alone, then triangle, star4 and star5, each counted
    # again in full on the portable path
    portable = [line for line in out if "] tiny-er portable " in line]
    assert len(portable) == 4
    assert all("== served" in line for line in portable)
    assert kinds and all("pallas=0" in fp for fp in kinds)


def test_partial_count_replay_must_match(monkeypatch):
    """A portable count cut after some dispatches is replayed on the
    default path; the same raw total and spans must come out."""
    from repro.configs.graphpi import get_pattern
    from repro.core.executor import ExecutorConfig, Matcher, device_graph
    from repro.core.plan import build_plan
    from repro.core.restrictions import generate_restriction_sets
    from repro.graph.datasets import named_dataset

    monkeypatch.setattr(chip_smoke, "assert_fused", lambda fp, hlo=None: 0)
    graph = named_dataset("tiny-er")
    tri = get_pattern("triangle")
    plan = build_plan(tri, (0, 1, 2),
                      generate_restriction_sets(tri, max_sets=1)[0])
    cfg = ExecutorConfig(capacity=256, use_pallas=False)
    arrays = device_graph(graph)
    state, res = Matcher(graph, plan, cfg, arrays=arrays).count_partial(
        max_dispatches=3)
    assert res is None and state.spans          # a cut count
    assert chip_smoke.replay_on_default_path(graph, plan, cfg, arrays,
                                             state) >= 0
    state.total += 1
    with pytest.raises(AssertionError):
        chip_smoke.replay_on_default_path(graph, plan, cfg, arrays, state)


@pytest.mark.parametrize("alone", [False, True], ids=["cpu", "alone"])
def test_script_fails_without_chip_or_repo(tmp_path, alone):
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
