"""Stub fleet replica for manager tests: the full process contract of
``bench serve --serve-http`` (admin surface, SIGTERM → record-on-stdout
→ exit 0) with no engine and no jax, so a spawn costs milliseconds.

Run as::

    python tests/_fleet_worker.py --admin-port 12345 --name r0 [--crash-after S]

The admin surface is a real :class:`AdminServer` in exporter mode —
``/healthz`` / ``/readyz`` / ``/snapshot`` behave as the manager and
router expect. On SIGTERM the worker prints its serving record as the
last stdout line (the ``last_json_line`` reap convention) and exits 0.
``--crash-after`` simulates a crash-on-boot / mid-life death: exit 17
with no record after that many seconds.
"""

import argparse
import json
import os
import signal
import sys
import threading
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--admin-port", type=int, required=True)
    ap.add_argument("--name", default="worker")
    ap.add_argument("--role", default="serve")
    ap.add_argument("--crash-after", type=float, default=None)
    args = ap.parse_args()

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from distributed_sddmm_tpu.obs.httpexp import AdminServer

    served = {"n": 0}

    def snapshot():
        return {
            "name": args.name, "depth_frac": 0.0, "burn_rate": 0.0,
            "buckets": {"batch": [2, 4], "inner": [4, 8]},
            "served": served["n"],
        }

    def submit(payload, tenant="default", serial=False, timeout_s=30.0):
        served["n"] += 1
        return {"echo": payload, "by": args.name, "serial": serial}

    if args.crash_after is not None:
        # Crash-on-boot: die before the admin surface ever comes up,
        # so readiness can never be (transiently) observed.
        time.sleep(args.crash_after)
        return 17  # unplanned death: no record on stdout

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())

    server = AdminServer(
        snapshot_fn=snapshot, submit_fn=submit, port=args.admin_port,
    ).start()
    # Poll rather than block: a process-directed SIGTERM may land on a
    # server thread (as it tends to right after a SIGCONT), and Python runs
    # the handler only once the main thread is back in the interpreter.
    while not stop.wait(0.1):
        pass
    server.stop()
    record = {
        "app": "fleet-worker-stub", "name": args.name, "role": args.role,
        "served": served["n"],
        "tuner_armed": os.environ.get("DSDDMM_TUNER") == "1",
    }
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
