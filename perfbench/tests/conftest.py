"""CPU tests of the benchmark itself, at sizes a test run can hold.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

# every cell cut to a size the Pallas interpreter runs in seconds; widths
# shrink here only, never in the cells themselves
TINY_CONFIG = {"docs": 150, "vocab": 800, "topics": 32, "block_tokens": 512,
               "hot_words": 50, "model_tokens": 200000,
               "length": {"dist": "lognormal", "mean": 40, "sigma": 0.75,
                          "min": 4}}
TINY_TRAFFIC = {"max_sweeps": 2, "rate_per_s": 10, "num_sweeps": 6,
                "burnin": 2, "max_len": 128, "drain_s": 60}


# The serving cell is not in BENCHMARK.json yet (PERF.md, Open questions);
# its runner is tested through this entry, as a later cell would add it.
SERVE = "nytimes-k1024.serve-poisson"


def spec_with_serving(spec: dict) -> dict:
    spec = dict(spec)
    spec["workloads"] = spec["workloads"] + [
        {"name": SERVE, "config": "nytimes-k1024", "traffic": "serve-poisson",
         "chips": 1, "why": "open-loop fold-in"}]
    spec["end_to_end"] = spec["end_to_end"] + [
        {"name": n, "unit": u, "better": b, "bound": 0.1,
         "source": "host_clock", "workloads": [SERVE]}
        for n, u, b in (("foldin_p95_ms", "ms", "lower"),
                        ("foldin_docs_per_s", "docs/s", "higher"))]
    return spec
