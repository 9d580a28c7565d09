"""The one general load generator: callers in a closed loop over the
partitions, driven by a traffic file's parameters.

``callers`` threads each submit the next query when their last has answered.
The queries take the partitions in turn, in an order drawn from the seed (the
same partitions for every seed, in another order), from one shared counter, so
no query reads what the one before it read.  No query is submitted after
``seconds``; those in flight are waited for, and the window ends when the last
of them has answered, so the window's time covers all its work."""

import threading
import time

import numpy as np

from benchmark import lib


def partition_order(seed, partitions):
    rng = np.random.default_rng(lib.seed_words(seed, 4, salt=7))
    return [int(p) for p in rng.permutation(partitions)]


def run_window(traffic, seconds, order, do_query, first_q=0):
    """``do_query(caller, q, part)`` answers one query or raises.  Returns the
    records of all queries and the window's start and end."""
    if traffic.get("loop") != "closed":
        raise lib.BenchError(f"loop {traffic.get('loop')!r}: the generator "
                             "knows 'closed'")
    callers = int(traffic["callers"])
    lock = threading.Lock()
    counter = [first_q]
    records = []
    t_start = time.perf_counter()
    deadline = t_start + float(seconds)

    def caller(i):
        while True:
            with lock:
                if time.perf_counter() >= deadline:
                    return
                q = counter[0]
                counter[0] += 1
            part = order[q % len(order)]
            rec = {"q": q, "part": part, "caller": i, "ok": False}
            rec["t0"] = time.perf_counter()
            try:
                rec["result"] = do_query(i, q, part)
                rec["ok"] = True
            except Exception as e:  # a failed query counts, the run goes on
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec["t1"] = time.perf_counter()
            with lock:
                records.append(rec)
            if not rec["ok"]:
                return   # a caller whose query failed submits no more

    threads = [threading.Thread(target=caller, args=(i,),
                                name=f"bench-caller-{i}") for i in
               range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = max([r["t1"] for r in records], default=time.perf_counter())
    records.sort(key=lambda r: r["q"])
    return records, t_start, t_end
