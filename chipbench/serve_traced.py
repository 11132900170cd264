"""The server under a profiler: ``run.py --trace 1`` starts this in place of
``python -m zipkin_tpu.server``.

It runs the same server ``main()`` (``zipkin_tpu/server/__main__.py``) with
the same arguments. On SIGUSR1, sent by ``run.py`` in the middle of the
window, a thread starts ``jax.profiler``, sleeps for the slice, stops it and
writes ``<trace-dir>/done.json``. Only the process that holds the chip can
trace it, so this cannot live in ``run.py``.

Everything sits under the ``__name__`` guard, JAX's import too: the server's
parse workers are ``spawn``ed and re-import this file as ``__mp_main__``, and
a worker that touched JAX would fight the server for the chip.
"""

import sys

if __name__ == "__main__":
    import argparse
    import json
    import os
    import runpy
    import signal
    import threading
    import time

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--trace-seconds", type=float, default=4.0)
    args, rest = ap.parse_known_args()

    def trace_slice() -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python frames would swamp the file
        os.makedirs(args.trace_dir, exist_ok=True)
        t0 = time.monotonic()
        jax.profiler.start_trace(args.trace_dir, profiler_options=opts)
        time.sleep(args.trace_seconds)
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        tmp = os.path.join(args.trace_dir, "done.tmp")
        with open(tmp, "w") as f:
            json.dump({"start": t0, "stop": t1,
                       "written": time.monotonic()}, f)
        os.replace(tmp, os.path.join(args.trace_dir, "done.json"))

    def on_usr1(_signum, _frame) -> None:
        threading.Thread(target=trace_slice, daemon=True).start()

    signal.signal(signal.SIGUSR1, on_usr1)
    # ``python -m`` puts the working directory (the checkout's root, as the
    # launcher starts us) on the path; a script gets its own directory
    sys.path[0] = os.getcwd()
    sys.argv = ["zipkin_tpu.server"] + rest
    runpy.run_module("zipkin_tpu.server", run_name="__main__")
