#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

On a TPU: a traced window (``bench.window``) holding two campaigns
(``bench.campaign``) of one jitted program and one call of the Pallas AnEn
distance kernel each, with host sleeps inside and between the campaigns,
so the trace has device ops, modules, idle gaps and the benchmark's
annotations. Prints the sleeps' lengths as JSON.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from repro.kernels.anen_distance import anen_distance

    step = jax.jit(lambda x: jnp.tanh(x @ x.T).sum(axis=0))
    x = jnp.ones((1024, 1024), jnp.float32)
    f_hist = jnp.ones((365, 3, 1024), jnp.float32)
    f_now = jnp.ones((3, 1024), jnp.float32)
    interpret = jax.default_backend() != "tpu"
    for _ in range(2):   # compile outside the trace
        step(x).block_until_ready()
        anen_distance(f_hist, f_now, interpret=interpret).block_until_ready()
    sleeps = {"inside_s": 0.05, "between_s": 0.1}
    with tempfile.TemporaryDirectory() as log_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.campaign"):
                    step(x).block_until_ready()
                    time.sleep(sleeps["inside_s"])
                    anen_distance(f_hist, f_now,
                                  interpret=interpret).block_until_ready()
                time.sleep(sleeps["between_s"])
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        shutil.copy(path, out)
    print(json.dumps(sleeps))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
