"""``chip_smoke.py`` off the chip, the placeable compile cache, and the
node-memory default that no longer guesses for an accelerator.

The smoke itself only means something on a TPU; these tests hold its
contract on the CPU backend: without a TPU it fails and prints no result,
and only the explicit rehearsal options let it run here.
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, like one chip
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_fails_without_a_tpu():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_cpu_rehearsal_at_tiny():
    proc = _run_smoke("--allow-cpu", "--schema", "tiny",
                      "--queries", "6,1,3,18,13")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": lines[-1]["device"]["kind"],
        "count": 1}}
    assert '"platform": "tpu"' not in proc.stdout
    served = [l["query"] for l in lines if "query" in l]
    assert served == ["q6", "q1", "q3", "q18", "q13",
                      "grouped_int32_states", "many_groups_int32_states"]
    # four groups reduce densely; 1,024 a page take the scatter branch
    paths = {l["query"]: l["grouping_path_counts"] for l in lines
             if "query" in l}
    assert paths["grouped_int32_states"]["dense"] == \
        paths["grouped_int32_states"]["hash"]
    assert "dense" not in paths["many_groups_int32_states"]


def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    from trino_tpu.compile_cache import compile_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == os.path.join(REPO, ".jax_cache")


def test_default_node_memory_bytes_raises_for_a_silent_accelerator(
        monkeypatch):
    import jax

    from trino_tpu.exec.memory import default_node_memory_bytes

    def device(platform, stats):
        return types.SimpleNamespace(platform=platform,
                                     device_kind=f"fake {platform}",
                                     memory_stats=lambda: stats)

    monkeypatch.setattr(jax, "local_devices",
                        lambda: [device("tpu", None)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        default_node_memory_bytes()
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [device("tpu", {"bytes_limit": 1 << 34})])
    assert default_node_memory_bytes() == 1 << 34
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [device("cpu", None)])
    assert default_node_memory_bytes(fallback=123) == 123
