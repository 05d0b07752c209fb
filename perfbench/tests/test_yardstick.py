"""The work counts against a hand count, and the trace reduction on a
small trace recorded on a TPU v5e (``data/small.xplane.pb``: two calls of
a program holding one alias-build and one MH-sampler kernel, 10 ms apart,
inside a ``bench.window`` annotation)."""
import json
import os
import re

import pytest

import tracing
import work

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _kernel(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    ns = {}
    exec(open(path).read(), ns)
    return ns["KERNEL"]


def test_work_counts_match_a_hand_count():
    # training, one token, 2 MH steps: w d z z' (16) + current n_wk n_dk
    # n_k (12) + 2 x [alias 8 + proposed counts 12 + doc z 4 + counts 12]
    assert work.mh_sample(1, 2) == work.Work(100, 80)
    # a moved token updates 3 counts for both topics: 6 x 8 B
    assert work.mh_sample(1, 2, changed=1).bytes == 148
    # fold-in: w z z' (12) + n_wk n_k (8) + 2 x [8 + 8 + 4 + 8]
    assert work.mh_sample(1, 2, frozen=True) == work.Work(76, 80)
    # Vose over one row of 4: read 4 counts, write 4 probs + 4 aliases,
    # read n_k once
    assert work.alias_build(1, 4) == work.Work(4 * 12 + 4 * 4, 16)
    assert work.sweep(1, 1, 1, 4, 2) == work.Work(148 + 64, 80 + 16)


def test_least_time_is_the_larger_bound():
    peaks = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 1000.0}
    assert work.Work(200, 1000).least_seconds(peaks) == 2.0
    assert work.Work(50, 4000).least_seconds(peaks) == 4.0


def test_peaks_table_is_keyed_by_device_kind():
    import harness
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v99")


@pytest.fixture(scope="module")
def small():
    return tracing.TraceSummary.from_file(
        os.path.join(HERE, "data", "small.xplane.pb"))


def test_trace_reduction_reads_the_device_plane(small):
    assert list(small.planes) == ["/device:TPU:0"]
    assert 0.2 < small.window_s < 0.5
    assert 0.0 < small.busy_s < small.window_s
    # the 10 ms sleeps between the two calls are idle
    assert small.window_s - small.busy_s > 0.01
    gaps = small.breakdown()["idle_gaps"]
    assert gaps and len(gaps) <= 10
    assert len(small.breakdown()["device_ops"]) == 10


def test_kernel_names_match_the_kernels_of_the_trace(small):
    mh = small.kernel_seconds(_kernel("mh_sample_roofline.train"), "mh")
    alias = small.kernel_seconds(_kernel("alias_build_roofline.train"),
                                 "alias")
    assert mh and alias and mh + alias < small.busy_s
    assert _kernel("mh_sample_roofline.serve") == \
        _kernel("mh_sample_roofline.train")
    hit = [op for op in small.ops()
           if re.search(_kernel("mh_sample_roofline.train"), op)]
    assert len(hit) == 1 and "s32[1,2048]" in hit[0]


def test_a_renamed_kernel_reads_nothing_not_zero(small):
    assert small.kernel_seconds(r"no_such_kernel", "renamed") is None


def test_spec_names_files_that_exist():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
