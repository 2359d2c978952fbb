"""The harness refuses anything but enough TPU chips; the test steers the
device check itself."""
import types

import pytest

from bench import harness


def devs(platform, n):
    return [types.SimpleNamespace(platform=platform, device_kind="x")] * n


@pytest.mark.parametrize("platform", ["cpu", "gpu"])
def test_refuses_non_tpu(platform):
    with pytest.raises(harness.DeviceError, match="no TPU"):
        harness.check_devices(devs(platform, 4), 1)


def test_refuses_too_few_chips():
    with pytest.raises(harness.DeviceError, match="needs 4 chips"):
        harness.check_devices(devs("tpu", 1), 4)


def test_accepts_enough_tpu_chips():
    harness.check_devices(devs("tpu", 4), 4)
    harness.check_devices(devs("tpu", 1), 1)


def test_entry_point_exits_without_result_on_cpu(capsys):
    """On this CPU-only host ``run.py`` exits 3 and prints no result."""
    from bench import run
    rc = run.main(["--workload", "qwen3-0.6b.longdoc", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 3 and out.out == "" and "no TPU" in out.err


def test_entry_point_refuses_unknown_cell(capsys):
    from bench import run
    assert run.main(["--workload", "no.such", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_peaks_unknown_device_is_an_error():
    from bench import peaks
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
