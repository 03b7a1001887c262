"""The memory figures of a run: live arrays plus the running program's
scratch, of the chip that held most, and their readers."""

import pytest

from benchmarks.harness import device


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def _stats(live, scratch, now=0):
    return {"peak_bytes_in_use": live, "peak_bytes_reserved": scratch,
            "bytes_in_use": now, "bytes_reserved": scratch}


@pytest.mark.parametrize("stats, want", [
    ([_stats(700, 4000)], (700, 4000)),
    # the fullest chip is the one whose live + scratch is largest
    ([_stats(900, 100), _stats(700, 4000)], (700, 4000)),
    # a backend that keeps no figures (the CPU) reads 0, not an error
    ([None], (0, 0)),
    ([{}], (0, 0)),
])
def test_split_and_peak(stats, want):
    devices = [_Dev(s) for s in stats]
    assert device.memory_split(devices) == want
    assert "in use" in device.memory_brief(devices)


@pytest.mark.parametrize("reader, key", [
    ("hbm_peak_mb", "peak_bytes"), ("hbm_live_mb", "live_bytes"),
    ("hbm_scratch_mb", "scratch_bytes")])
def test_readers_report_mb_or_nothing(real_spec, reader, key):
    read = real_spec.module("metrics", reader).read
    assert read({key: 4_836_261_888}) == pytest.approx(4836.261888)
    assert read({key: 0}) is None
