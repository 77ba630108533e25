"""Launcher of the node child: `python -m seaweedfs_tpu <args>` with two
things only the process that holds the chip can do.

With BENCH_TRACE=1 a daemon thread watches the run's work directory
(BENCH_WORK): when the file `trace.on` appears it starts the JAX
profiler into `<work>/trace`, when the file is removed it stops it and
writes `trace.done`. With BENCH_TRACE unset no thread is started.

When the node exits, the peak device memory of the fullest chip goes to
`<work>/device_memory.json` (null where the backend reports none).
"""

import json
import os
import runpy
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def publish(path: str, text: str) -> None:
    """Whole or not at all: the harness polls for these files."""
    with open(path + ".tmp", "w") as f:
        f.write(text)
    os.replace(path + ".tmp", path)


def watch_trace_flag(work: str) -> None:
    import jax

    flag = os.path.join(work, "trace.on")
    while not os.path.exists(flag):
        time.sleep(0.01)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # device planes and XLA's own host events only
    jax.profiler.start_trace(os.path.join(work, "trace"), profiler_options=options)
    publish(os.path.join(work, "trace.started"), repr(time.time()))
    while os.path.exists(flag):
        time.sleep(0.01)
    t_stop = time.time()
    jax.profiler.stop_trace()
    publish(os.path.join(work, "trace.done"), repr(t_stop))


def write_device_memory(work: str) -> None:
    peak = None
    if "jax" in sys.modules:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
        peak = max(peaks) if peaks else None
    publish(os.path.join(work, "device_memory.json"), json.dumps({"memory_peak_bytes": peak}))


def main() -> None:
    work = os.environ["BENCH_WORK"]
    sys.path.insert(0, ROOT)
    if os.environ.get("BENCH_TRACE") == "1":
        threading.Thread(target=watch_trace_flag, args=(work,), daemon=True).start()
    try:
        runpy.run_module("seaweedfs_tpu", run_name="__main__", alter_sys=True)
    finally:
        write_device_memory(work)


if __name__ == "__main__":
    main()
