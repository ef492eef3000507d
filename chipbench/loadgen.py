"""The open-loop load generator: a process of its own, off the chip.

    python3 chipbench/loadgen.py <address> <replica name>

The chip belongs to the replica's process, so the generator never initialises
an accelerator backend (its parent sets ``JAX_PLATFORMS=cpu``).  It reads one
JSON line from standard input (the schedule of ``chipbench/traffic.py``, the
seed, the vocabulary), draws every prompt, connects the program's own
``ServeClient`` to the replica and prints ``READY``.  A second line gives
``t0``, on the system-wide monotonic clock both processes read.  Request i is
sent at ``t0 + due_s`` whether or not earlier ones have finished, and timed
from when it was DUE.  When every counted request has been answered (and ``min_s`` has passed), or at
``stop_s``, it prints one JSON line of per-request records and leaves.
"""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(address: str, replica: str) -> int:
    import numpy as np

    from chipbench import traffic
    from moolib_tpu.rpc import Rpc
    from moolib_tpu.serving import ServeClient

    job = json.loads(sys.stdin.readline())
    schedule = job["schedule"]
    prompts = [traffic.prompt_tokens(job["seed"], r["index"], r["prompt_len"], job["vocab"])
               for r in schedule]
    rpc = Rpc()
    rpc.set_name("chipbench_loadgen")
    rpc.connect(address)
    client = ServeClient(rpc, fn="generate", replicas=[replica],
                         deadline_s=job["deadline_s"], attempt_timeout=job["deadline_s"],
                         max_attempts=1)
    print("READY", flush=True)
    t0 = json.loads(sys.stdin.readline())["t0"]
    stop_at = t0 + job["stop_s"]
    min_at = t0 + job["min_s"]  # keep offering at least this long (a traced tail)
    lock = threading.Lock()
    counted_left = [sum(1 for r in schedule if r["counted"])]
    all_done = threading.Event()

    def on_done(rec, prompt, fut):
        rec["done"] = time.monotonic()
        try:
            out = np.asarray(fut.result())
            rec["n_out"] = int(out.shape[0] - prompt.shape[0])
            rec["ok"] = bool(rec["n_out"] == rec["budget"]
                             and np.array_equal(out[:prompt.shape[0]], prompt))
        except Exception as e:  # noqa: BLE001 - every failure is a failed request
            rec["ok"], rec["error"] = False, repr(e)[:200]
        if rec["counted"]:
            with lock:
                counted_left[0] -= 1
                if counted_left[0] == 0:
                    all_done.set()

    def wait_until(t: float) -> bool:
        """Sleep, then spin the last 2 ms, until ``t``; False once the run is over."""
        while True:
            now = time.monotonic()
            if (all_done.is_set() and now >= min_at) or now >= stop_at:
                return False
            if now >= t:
                return True
            if t - now > 0.003:
                all_done.wait(min(t - now - 0.002, 0.05))

    records = []
    for r, prompt in zip(schedule, prompts):
        due = t0 + r["due_s"]
        if not wait_until(due):
            break
        rec = {"index": r["index"], "counted": r["counted"], "due": due,
               "budget": r["budget"], "prompt_len": r["prompt_len"],
               "sent": time.monotonic(), "done": None, "ok": False}
        records.append(rec)
        fut = client.submit(prompt, r["budget"])
        fut.add_done_callback(lambda f, rec=rec, prompt=prompt: on_done(rec, prompt, f))
    while wait_until(stop_at):
        pass
    with lock:
        out = [dict(r) for r in records if r["counted"]]
    print("RESULT " + json.dumps({"records": out, "sent_total": len(records),
                                  "client": client.stats()}), flush=True)
    sys.stdin.readline()  # the parent closes the replica, then lets us go
    client.close()
    rpc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
