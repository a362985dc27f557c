"""The bench harness's failure contract: a config that throws is
recorded as an error and the run exits non-zero, the CPU backend is
refused, and no path replays numbers from an earlier run.
"""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench  # noqa: E402


class TestProbe:
    def test_cpu_backend_is_unreachable(self):
        """The tests run on the CPU backend: the probe must refuse it
        rather than time a cpu round trip as 'tpu'."""
        ok, why = bench._probe_device(timeout_s=30)
        assert not ok
        assert "cpu backend" in why

    def test_no_retry_sentinel_skips_backoff(self, monkeypatch):
        """A cpu backend is settled for the process lifetime: the probe
        must give up immediately (no 45 s of futile backoff) and strip
        the sentinel from the reason."""
        calls = []

        def fake_probe(timeout_s):
            calls.append(1)
            return False, bench._NO_RETRY + "cpu backend"

        monkeypatch.setattr(bench, "_probe_device", fake_probe)
        monkeypatch.setattr(
            bench.time, "sleep", lambda s: (_ for _ in ()).throw(
                AssertionError("backoff slept on a no-retry failure")
            )
        )
        ok, why = bench._probe_with_retries(attempts=3, timeout_s=1)
        assert not ok
        assert why == "cpu backend"
        assert len(calls) == 1

    def test_transient_failure_still_retries(self, monkeypatch):
        seq = [(False, "timeout"), (True, None)]

        def fake_probe(timeout_s):
            return seq.pop(0)

        monkeypatch.setattr(bench, "_probe_device", fake_probe)
        monkeypatch.setattr(bench.time, "sleep", lambda s: None)
        ok, why = bench._probe_with_retries(attempts=3, timeout_s=1)
        assert ok and why is None

    def test_main_exits_nonzero_on_cpu(self, monkeypatch, capsys):
        monkeypatch.setattr(sys, "argv", ["bench.py"])
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code == 1
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["value"] is None and "unreachable" in out["error"]

    def test_no_cache_replay_left(self):
        for name in ("CACHE_PATH", "_load_cache", "_save_cache"):
            assert not hasattr(bench, name)
        assert not (pathlib.Path(bench.__file__).parent
                    / "bench_cache.json").exists()


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        import jax

        before = (jax.config.jax_compilation_cache_dir,
                  jax.config.jax_hlo_source_file_canonicalization_regex)
        yield
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          before[1])

    def test_env_dir_is_honoured(self, tmp_path, monkeypatch,
                                 restore_cache_dir):
        import jax

        from celestia_tpu.ops import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    def test_unset_env_uses_fixed_in_checkout_path(self, monkeypatch,
                                                   restore_cache_dir):
        from celestia_tpu.ops import _machine_fingerprint, enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = pathlib.Path(bench.__file__).resolve().parent
        want = str(repo / ".jax_cache" / _machine_fingerprint())
        assert enable_compile_cache() == want
        assert enable_compile_cache() == want  # fixed: no pid, time or tmp


class TestChipSmoke:
    def test_main_exits_nonzero_on_cpu(self, capsys):
        import chip_smoke

        assert chip_smoke.main([]) != 0
        captured = capsys.readouterr()
        assert '"ok"' not in captured.out
        assert "no TPU" in captured.err

    def test_four_chips_exits_nonzero_on_cpu(self, capsys):
        import chip_smoke

        assert chip_smoke.main(["--four-chips"]) != 0
        assert '"ok"' not in capsys.readouterr().out


class TestRunConfig:
    def test_success_marks_measured(self):
        configs, prov = {}, {}
        bench._run_config(configs, prov, "x", lambda: {"v": 1, "parity": True})
        # every measured entry carries the host stamp (cpus, n_devices)
        assert configs["x"]["v"] == 1 and configs["x"]["parity"] is True
        import os

        assert configs["x"]["cpus"] == os.cpu_count()
        assert "n_devices" in configs["x"]
        assert prov["x"] == "measured"

    def test_stamp_does_not_override_explicit_fields(self):
        configs, prov = {}, {}
        bench._run_config(
            configs, prov, "x", lambda: {"cpus": 99, "n_devices": 3})
        assert configs["x"]["cpus"] == 99
        assert configs["x"]["n_devices"] == 3

    def test_failure_records_error(self):
        def boom():
            raise ValueError("no device")

        configs, prov = {}, {}
        bench._run_config(configs, prov, "x", boom)
        assert prov["x"] == "failed"
        assert "no device" in configs["x"]["error"]

    def test_parity_failure_flagged(self):
        configs, prov = {}, {}
        bench._run_config(
            configs, prov, "x", lambda: {"v": 1, "parity": False}
        )
        assert prov["x"] == "parity-failed"

    def test_watchdog_bounds_a_hung_config(self, monkeypatch):
        """A config that blocks past the deadline is aborted and
        recorded as failed."""
        import time as _time

        monkeypatch.setattr(bench, "CONFIG_TIMEOUT_S", 1)

        def hang():
            _time.sleep(5)
            return {"v": 0}

        configs, prov = {}, {}
        t0 = _time.monotonic()
        bench._run_config(configs, prov, "x", hang)
        assert _time.monotonic() - t0 < 4
        assert prov["x"] == "failed"
        assert "exceeded" in configs["x"]["error"]


class TestFinish:
    @pytest.mark.parametrize("prov,code", [
        ({"a": "measured", "b": "failed"}, "bench configs failed"),
        ({"a": "parity-failed"}, "DAH mismatch"),
    ])
    def test_throwing_or_wrong_config_exits_nonzero(self, prov, code,
                                                    capsys):
        configs = {n: {"error": "boom"} for n in prov}
        with pytest.raises(SystemExit) as exc:
            bench._finish({"metric": "m", "value": None}, configs, prov,
                          "DAH mismatch")
        assert code in str(exc.value.code)
        out = json.loads(capsys.readouterr().out)
        assert set(out["failed_configs"]) == {
            n for n, v in prov.items() if v != "measured"}

    def test_all_measured_exits_cleanly(self, capsys):
        bench._finish({"metric": "m", "value": 1.0}, {"a": {"v": 1}},
                      {"a": "measured"}, "DAH mismatch")
        out = json.loads(capsys.readouterr().out)
        assert out["value"] == 1.0 and "failed_configs" not in out
