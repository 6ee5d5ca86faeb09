"""The smoke, rehearsed on the CPU: ``chip_smoke.py --scale tiny`` runs the
same control flow it runs on the chip (Pallas kernels interpreted, sizes
in the hundreds) and must reach its last line with every phase's answers
equal to the host's — and without the rehearsal flag it must refuse at
the ``device`` phase, because this process has no TPU."""

from __future__ import annotations

import json
import signal

import jax
import pytest

import chip_smoke

#: the rehearsal may not hang the suite
TIME_LIMIT_S = 60


@pytest.fixture
def smoke_sandbox(tmp_path, monkeypatch):
    """Caches under a temp checkout (never the repo), the process-global
    jax cache config restored afterwards, and a hard time limit."""
    monkeypatch.setattr(chip_smoke, "HERE", str(tmp_path))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}

    def too_slow(signum, frame):
        raise TimeoutError(f"chip_smoke rehearsal passed {TIME_LIMIT_S} s")

    old = signal.signal(signal.SIGALRM, too_slow)
    signal.alarm(TIME_LIMIT_S)
    try:
        yield tmp_path
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        from jax.experimental.compilation_cache import (
            compilation_cache as cc,
        )

        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_tiny_rehearsal_reaches_the_last_line(smoke_sandbox, capsys,
                                              monkeypatch):
    # a device whose compiler refuses every BFS program wider than 64
    # seeds: the rehearsal walks the path the chip does — the executor
    # caps BFS batches and the burst must fill that bucket
    from hypergraphdb_tpu.serve.runtime import DeviceExecutor

    real = DeviceExecutor._bfs_program

    def compiler(self, view, bucket):
        if bucket > 64:
            self.aot.stats.misses += 1      # asked, and nothing to cache
            raise RuntimeError("RESOURCE_EXHAUSTED: ran out of hbm")
        return real(self, view, bucket)

    monkeypatch.setattr(DeviceExecutor, "_bfs_program", compiler)
    monkeypatch.setattr(DeviceExecutor, "_device_memory_is_bounded",
                        lambda self: True)
    assert chip_smoke.main(["--scale", "tiny", "--seed", "1"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    phases = {x["phase"]: x for x in lines[:-1]}
    assert list(phases) == ["device", "kernels", "serve", "summary"]
    assert all(x["ok"] for x in phases.values())
    # (a) every phase's answers matched the host
    k = phases["kernels"]
    assert k["pattern"]["equal_host"] and k["gather_or"]["equal_host"]
    assert k["intersect"]["equal_host"]
    assert k["bfs"]["equal_host_seeds"] == 64
    assert k["bfs"]["legs_equal"]       # Pallas-gather leg == XLA-gather leg
    s = phases["serve"]
    for stage in ("stage1", "stage2", "stage3"):
        assert s[stage]["all_equal_host"]
        assert s[stage]["also_equal_find_all"] > 0
    assert s["stage1"]["served_by_host"] == 0
    # the burst: capped at 64, ran full at 64, nothing wider anywhere
    assert s["config"]["bfs_bucket_cap"] == 64
    assert s["burst"]["all_equal_host"]
    assert s["burst"]["bfs_buckets"] == {
        "64": {"requests": s["burst"]["requests"], "widest_batch": 64}}
    assert set(s["bfs_entry_by_bucket"]) == {"64"}
    assert any("BFS bucket 256 declined" in w for w in s["warnings"])
    assert s["stats"]["device_dispatches"] > 0
    assert s["stats"]["errors"] == 0 and s["stats"]["breaker_trips"] == 0
    # the second runtime loaded everything but the refusal, asked again
    assert s["aot_warm"]["aot"]["misses"] == 1
    assert s["aot_warm"]["aot"]["disk_hits"] > 0
    assert s["compaction"]["passes"] >= 1
    # the caches went under the (sandboxed) checkout and nowhere else
    assert phases["device"]["compile_cache_dir"] == str(
        smoke_sandbox / ".jax_cache")
    assert (smoke_sandbox / ".jax_cache").is_dir()
    assert (smoke_sandbox / ".aot_cache").is_dir()
    # (c) the last line's keys are exactly ok and device
    assert set(lines[-1]) == {"ok", "device"}
    assert lines[-1]["ok"] is True
    assert set(lines[-1]["device"]) == {"platform", "kind", "count"}
    assert lines[-1]["device"]["platform"] == "cpu"     # a rehearsal


def test_without_the_rehearsal_flag_it_stops_at_the_device_phase(
        smoke_sandbox, capsys):
    # (b) not told it is a rehearsal: non-zero at the device check, and
    # no result on stdout
    assert chip_smoke.main(["--seed", "1"]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "not a TPU" in out.err


def test_tiny_scale_is_refused_unless_the_caller_asked_for_cpu(
        smoke_sandbox, monkeypatch, capsys):
    monkeypatch.delenv("JAX_PLATFORMS")
    assert chip_smoke.main(["--scale", "tiny"]) == 2
    assert capsys.readouterr().out == ""
