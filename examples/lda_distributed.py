"""Distributed LDA: the paper's architecture on an SPMD mesh.

Workers (all mesh shards) sample their document partitions; servers (the
model axis) hold cyclic rows of n_wk.  The count tables enter the sweep
as ``repro.ps`` handles on an ``SpmdBackend`` (built by
``PSClient.create(axis_name=..., model_axis=...)`` inside
``repro.api.session.make_spmd_sweep`` -- the launcher is a thin
argv -> ``LDAJob`` translator): pulls are all-gathers over the server
axis, pushes one psum per merge group.  Runs on 8 fake host devices
here; on a pod the same code uses make_production_mesh().

  PYTHONPATH=src python examples/lda_distributed.py
"""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

if __name__ == "__main__":
    # eight forced host devices on the CPU backend; the device count must
    # be set before jax initialises -> exec the launcher in a fresh
    # interpreter (this is what a multi-host launcher does too)
    cmd = [sys.executable, "-m", "repro.launch.lda",
           "--devices", "8", "--mesh-model", "2",
           "--docs", "600", "--vocab", "1500", "-k", "30",
           "--sweeps", "30", "--eval-every", "10",
           # hybrid push route: hottest 200 words dense, cold tail as
           # coordinate deltas (paper section 3.3)
           "--staleness", "2", "--hot-words", "200"]
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    raise SystemExit(subprocess.call(cmd, env=env, cwd=ROOT))
