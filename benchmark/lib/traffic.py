"""The one general generator of serving traffic. A traffic mix is a
data file of parameters (``benchmark/traffic/<name>.json``); this reads
it and makes, from ``--seed`` alone, the requests the load generator
will send: an open-loop schedule of due times, or per-client queues for
a closed loop. The program receives only the generated requests.

The amount of work is fixed by the file, and the seed only decides
which request gets which draw: lengths are stratified over the
distribution's quantiles (one draw from each of ``n`` equal slices,
then shuffled) and an open loop sends exactly ``round(rate x seconds)``
requests at the order statistics of uniform times, which is a Poisson
process conditioned on its count. Two seeds then offer the same load
in a different order, so run-to-run spread measures the system and not
the dice. Where the order itself decides the work (a closed loop whose
window opens on a young replica: which lengths come first decides when
the longest context passes the step's next table width), the file
gives ``sizes_seed``: every run then offers the sizes that seed draws,
in its order, and ``--seed`` decides the tokens (and the weights) alone.

Length specifications: ``{"dist": "loguniform", "lo", "hi"}``,
``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` (values
outside ``lo``..``hi`` are clipped to the bound) and
``{"dist": "fixed", "value"}``."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile(spec: dict, q: np.ndarray) -> np.ndarray:
    """The length distribution's quantile function, as whole numbers."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(q.shape, int(spec["value"]), np.int64)
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if dist == "loguniform":
        x = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(min(max(v, 1e-9), 1 - 1e-9))
                      for v in q])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths, one from each of ``n`` equal slices of the
    distribution, in a seeded order."""
    q = (np.arange(n) + rng.random(n)) / n
    return rng.permutation(quantile(spec, q))


def _request(req_id: str, prompt_len: int, max_tokens: int, vocab: int,
             deadline_ms: float, rng: np.random.Generator) -> dict:
    return {"id": req_id,
            "prompt": rng.integers(0, vocab, int(prompt_len)).tolist(),
            "max_tokens": int(max_tokens), "temperature": 0.0,
            "deadline_ms": float(deadline_ms)}


def open_schedule(traffic: dict, seed: int, seconds: float,
                  vocab: int) -> list[dict]:
    """Requests of an open loop, each with ``due_s`` relative to the
    start of the measured window. Those with a negative ``due_s`` are
    sent during the warm-up, at the same rate, so that the window opens
    on a system in its steady state."""
    rng = np.random.default_rng([int(seed), 0x0BE7])
    rate = float(traffic["arrivals"]["rate_per_s"])
    warm = float(traffic["warmup_s"])
    n_warm, n_win = round(rate * warm), round(rate * seconds)
    due = np.concatenate([np.sort(rng.random(n_warm)) * warm - warm,
                          np.sort(rng.random(n_win)) * seconds])
    n = n_warm + n_win
    plens = draw_lengths(traffic["prompt_len"], n, rng)
    outs = draw_lengths(traffic["max_tokens"], n, rng)
    reqs = []
    for i in range(n):
        r = _request(f"o{i}", plens[i], outs[i], vocab,
                     traffic["deadline_ms"], rng)
        r["due_s"] = float(due[i])
        reqs.append(r)
    return reqs


def closed_queues(traffic: dict, seed: int, clients: int,
                  vocab: int) -> list[list[dict]]:
    """One queue of requests per client of a closed loop. With
    ``stagger_first_wave`` each client's first request asks for a
    seeded share of its drawn length, as if it had been running for a
    while: the slots then finish at different times from the start, as
    they do in the steady state, and not all at once. With
    ``sizes_seed`` the lengths, their order and the shares are that
    seed's in every run, and only the prompts' tokens are ``seed``'s."""
    if "sizes_seed" in traffic:
        sized = dict(traffic)
        queues = closed_queues(sized, sized.pop("sizes_seed"), clients, vocab)
        rng = np.random.default_rng([int(seed), 0x70CE])
        for queue in queues:
            for req in queue:
                req["prompt"] = rng.integers(
                    0, vocab, len(req["prompt"])).tolist()
        return queues
    rng = np.random.default_rng([int(seed), 0xC105])
    per_client = int(traffic["requests_per_client"])
    n = clients * per_client
    plens = draw_lengths(traffic["prompt_len"], n, rng)
    outs = draw_lengths(traffic["max_tokens"], n, rng)
    queues = []
    for c in range(clients):
        queue = []
        for j in range(per_client):
            i = c * per_client + j
            out = int(outs[i])
            if j == 0 and traffic.get("stagger_first_wave"):
                out = max(1, int(out * rng.uniform(0.05, 1.0)))
            queue.append(_request(f"c{c}-{j}", plens[i], out, vocab,
                                  traffic["deadline_ms"], rng))
        queues.append(queue)
    return queues


def warmup_lengths(traffic: dict) -> list[int]:
    """Prompt lengths that between them reach every prefill shape the
    mix can reach, for a replica that pads prompts to powers of two (or
    to anything coarser): both ends of the range and every power of two
    between them."""
    spec = traffic["prompt_len"]
    if spec["dist"] == "fixed":
        return [int(spec["value"])]
    lo, hi = int(spec["lo"]), int(spec["hi"])
    powers = [1 << k for k in range(lo.bit_length(), hi.bit_length())
              if lo < (1 << k) < hi]
    return sorted({lo, hi, *powers})
