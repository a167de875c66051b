"""Host PS-plane throughput microbench (no accelerator needed).

The SPMD plane's performance is the chip benchmark's (`benchmark/`);
this tool measures the OTHER plane — the process-separated TCP parameter-server service
that backs the async modes (MixedSync/HFA over real WAN deployments,
reference ps-lite Van/ZMQVan).  It drives W concurrent worker clients
push+pulling an N-MB tensor against one sync-mode server for R rounds
and reports aggregate goodput.

Run:  python tools/bench_service.py [--mb 4] [--workers 4] [--rounds 20]
Prints one JSON line, e.g.
  {"metric": "ps_plane_goodput", "push_pull_mb_s": ..., ...}

Methodology: per round every worker pushes its gradient (the server's
sync barrier merges all W pushes — reference DataHandleSyncDefault) and
pulls the merged value back, so one round moves (push + pull) x W x N MB
through the framed wire protocol, the priority send queue, and the
merge path.  Wall time is the max across workers per round, summed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from geomx_tpu.service.client import GeoPSClient  # noqa: E402
from geomx_tpu.service.server import GeoPSServer  # noqa: E402


def run(mb: float, workers: int, rounds: int) -> dict:
    n = int(mb * (1 << 20) // 4)
    server = GeoPSServer(num_workers=workers, mode="sync").start()
    clients = []
    try:
        clients = [GeoPSClient(("127.0.0.1", server.port), sender_id=i)
                   for i in range(workers)]
        grads = [np.full((n,), float(i + 1), np.float32)
                 for i in range(workers)]
        clients[0].init("w", np.zeros((n,), np.float32))
        # sync mode overwrites the value with each round's merged sum
        expect = workers * (workers + 1) / 2.0

        barrier = threading.Barrier(workers)
        # [round][worker] seconds: the goodput denominator is the sum of
        # per-round MAXIMA (the straggler defines a sync round), so
        # thread-spawn and barrier-wait time stay out of the measurement
        round_s = [[0.0] * workers for _ in range(rounds)]
        errs: list = []

        def worker(i):
            try:
                c = clients[i]
                for r in range(rounds):
                    barrier.wait()
                    t0 = time.perf_counter()
                    c.push("w", grads[i])
                    out = c.pull("w")
                    round_s[r][i] = time.perf_counter() - t0
                    assert out.shape == (n,)
                    # pin the merge itself: a sync round that dropped a
                    # worker's push would still move the same bytes
                    assert abs(float(out[0]) - expect) < 1e-4, out[0]
            except Exception as e:  # surface, don't hang the barrier
                errs.append(repr(e))
                barrier.abort()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(workers)]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_all
        if errs:
            raise RuntimeError(errs[0])

        stats = clients[0].wire_stats()
    finally:
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        server.stop()
    busy = sum(max(row) for row in round_s)
    moved_mb = 2 * workers * rounds * n * 4 / (1 << 20)  # push + pull
    return {
        "metric": "ps_plane_goodput",
        "tensor_mb": round(n * 4 / (1 << 20), 2),
        "workers": workers, "rounds": rounds,
        "push_pull_mb_s": round(moved_mb / busy, 1),
        "busy_s": round(busy, 3),
        "wall_s": round(wall, 3),
        "per_worker_mean_round_ms": round(
            1e3 * sum(sum(r) for r in round_s) / (workers * rounds), 2),
        "server_msgs": stats["msgs_received"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=float, default=4.0,
                    help="tensor size in MB (fp32)")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=20)
    args = ap.parse_args()
    print(json.dumps(run(args.mb, args.workers, args.rounds)), flush=True)


if __name__ == "__main__":
    main()
