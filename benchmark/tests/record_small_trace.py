"""Records tests/data/small_tpu.xplane.pb on the chip: three calls of one
jitted chain of two 2048 x 2048 matmuls and a while loop, so that the trace
holds fusions, a container operation and idle gaps between the calls.

    python benchmark/tests/record_small_trace.py <out dir>

The numbers in small_tpu.json are then read from it with peek_trace.py and
trace_reduce.py and checked by hand; the test holds the reduction to them.
"""

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(out):
    import jax
    import jax.numpy as jnp
    import trace_reduce

    @jax.jit
    def step(x):
        y = jnp.tanh(x @ x)
        return jax.lax.fori_loop(0, 4, lambda i, z: jnp.sin(z @ y), y)

    x = jnp.ones((2048, 2048), jnp.float32) * 0.01
    step(x).block_until_ready()
    tmp = os.path.join(out, "tmp_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    with jax.profiler.trace(tmp):
        for _ in range(3):
            step(x).block_until_ready()
            time.sleep(0.01)
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out, "small_tpu.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    red = trace_reduce.reduce_events(trace_reduce.device_events(dst))
    pattern = {"match": ["fusion"], "except": []}
    secs, names = trace_reduce.pattern_seconds(red["op_seconds"], pattern)
    json.dump({"devices": sorted(red["per_device_busy_s"]),
               "busy_s": red["busy_s"], "window_s": red["window_s"],
               "pattern": pattern, "pattern_s": secs,
               "pattern_names": len(names),
               "device": jax.devices()[0].device_kind},
              open(os.path.join(out, "small_tpu.json"), "w"), indent=1)
    print(os.path.getsize(dst), "bytes", red["busy_s"], red["window_s"],
          sorted(red["op_seconds"].items(), key=lambda kv: -kv[1])[:8])


if __name__ == "__main__":
    main(sys.argv[1])
