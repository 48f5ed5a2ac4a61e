"""Replay of a closed serving cell's schedule with no chip: how many
prefills a window holds, and what share of its token gaps hold a
prefill's stall, as the decode iteration shortens (no part of
graftcheck; run it from the root of a checkout, whose benchmark's own
traffic generator it drives).

    python -m distributedmnist_tpu.analysis.closed_replay \\
        benchmark/traffic/serve_reason_closed.json 64 36.2 32.7 25 20 16

The cell's own queues (``benchmark/lib/traffic.py::closed_queues`` with
the file's ``sizes_seed``), ``clients_per_slot`` clients a slot, one
token a live slot an iteration of the given ms, a prefill stalling the
loop for ``--prefill-ms`` by its bucket (PERF.md section 5 has the
latent cell's 45 / 72 / 126 ms), ``warmup_s`` of the file before a 40 s
window. PERF.md section 7 ("the latent serving cell's open ends", (1))
holds the readings: the share falls as the step shortens, so a faster
step does not push the 90th gap onto a stall."""

from __future__ import annotations

import argparse
import json
import sys


def replay(traffic: dict, slots: int, iter_ms: float, prefill_ms: dict,
           warm_s: float, window_s: float = 40.0, vocab: int = 1000) -> dict:
    from benchmark.lib.traffic import closed_queues
    clients = slots * traffic["clients_per_slot"]
    queues = closed_queues(traffic, 1, clients, vocab)
    taken = [0] * clients                 # requests a client has sent
    waiting, free = list(range(clients)), list(range(slots))
    live: dict[int, list] = {}            # slot -> [client, tokens left]
    now = 0.0
    iterations = prefills = gaps = stalled = 0
    while now < (warm_s + window_s) * 1e3:
        stall, admitted = 0.0, 0
        while free and waiting:
            client = waiting.pop(0)
            request = queues[client][taken[client]]
            taken[client] += 1
            stall += prefill_ms[next(b for b in sorted(prefill_ms)
                                     if len(request["prompt"]) <= b)]
            admitted += 1
            live[free.pop(0)] = [client, request["max_tokens"] - 1]
        now += stall + iter_ms
        if now >= warm_s * 1e3:
            iterations += 1
            prefills += admitted
            gaps += len(live)
            if admitted:
                stalled += len(live) - admitted
        for slot in list(live):
            live[slot][1] -= 1
            if live[slot][1] <= 0:
                client = live.pop(slot)[0]
                free.append(slot)
                if taken[client] < len(queues[client]):
                    waiting.append(client)
    return {"iter_ms": iter_ms, "iterations": iterations,
            "prefills": prefills,
            "gaps_that_hold_a_stall_pct": round(100 * stalled / gaps, 2)}


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("traffic_file")
    ap.add_argument("slots", type=int)
    ap.add_argument("iter_ms", type=float, nargs="+")
    ap.add_argument("--prefill-ms", default="512:45,1024:72,2048:126",
                    help="bucket:ms, ... (a prompt takes the first bucket "
                         "that holds it)")
    ap.add_argument("--warm-s", type=float, default=None,
                    help="default: the traffic file's warmup_s")
    args = ap.parse_args(argv)
    sys.path.insert(0, ".")
    with open(args.traffic_file) as f:
        traffic = json.load(f)
    prefill_ms = {int(b): float(ms) for b, ms in
                  (pair.split(":") for pair in args.prefill_ms.split(","))}
    warm_s = traffic.get("warmup_s", 12.0) if args.warm_s is None \
        else args.warm_s
    for iter_ms in args.iter_ms:
        print(json.dumps(replay(traffic, args.slots, iter_ms, prefill_ms,
                                warm_s)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
