"""The one general traffic generator: a serving cell's requests and their
arrival times from the parameters of its traffic file and the run's seed.
Plain Python (``random`` and ``math`` only), so that the load generator's
process needs neither numpy nor JAX.

Every seed gives the same set of prompt lengths, output lengths and
inter-arrival gaps, in another order: the values are the mid-quantiles of
their distributions (lognormal lengths, exponential gaps), and the seed
draws a uniformly random permutation of each set.  Given its sorted
values an independent sample *is* such a permutation, so runs of short
gaps (bursts) and of long prompts come as often as under independent
draws; what the fixed sets take away is only the chance that one seed
offers more work than another.  The ramp before the window and the window
itself each get sets of their own, so the window of every seed holds the
same requests, and ``attempted`` is one number for a cell."""

import math
import random
from statistics import NormalDist


def lognormal_quantiles(n, median, sigma, lo, hi):
    """``n`` whole numbers at the mid-quantiles of a lognormal with the
    given median and log-space ``sigma``, clipped to ``[lo, hi]``."""
    z = NormalDist()
    return [int(min(hi, max(lo, round(
        median * math.exp(sigma * z.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def exponential_quantiles(n, mean):
    """``n`` gaps at the mid-quantiles of an exponential distribution,
    scaled so that they sum to ``n * mean`` exactly."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n * mean / sum(raw)
    return [g * scale for g in raw]


def shuffled(values, rng):
    out = list(values)
    rng.shuffle(out)
    return out


def arrivals(n, span_s, rng):
    """``n`` arrival offsets inside ``[0, span_s)``: exponential gaps that
    sum to the span, in an order drawn from ``rng``; the stretch begins
    and ends half-way through the first gap."""
    gaps = shuffled(exponential_quantiles(n, span_s / n), rng)
    out, t = [], -0.5 * gaps[0]
    for g in gaps:
        t += g
        out.append(t)
    return out


def stretch(mix, vocab_size, rng, tag, begin_s, span_s):
    """The requests due in ``[begin_s, begin_s + span_s)``: dicts with
    ``due`` (seconds from the start of the run) and ``body`` (the JSON of
    ``POST /generate``), in order of ``due``."""
    n = int(round(mix["rate_per_s"] * span_s))
    if n < 1:
        return []
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    prompts = shuffled(lognormal_quantiles(n, p["median"], p["sigma"],
                                           p["min"], p["max"]), rng)
    outputs = shuffled(lognormal_quantiles(n, o["median"], o["sigma"],
                                           o["min"], o["max"]), rng)
    due = arrivals(n, span_s, rng)
    sampled_every = mix["sampled"]["every"]
    out = []
    for i in range(n):
        body = {"request_id": "%s%d" % (tag, i),
                "prompt": [rng.randrange(vocab_size)
                           for _ in range(prompts[i])],
                "max_new_tokens": outputs[i], "stream": True,
                "timeout": mix["request_timeout_s"]}
        if sampled_every and i % sampled_every == sampled_every - 1:
            body.update(temperature=mix["sampled"]["temperature"],
                        top_k=mix["sampled"]["top_k"],
                        top_p=mix["sampled"]["top_p"],
                        seed=rng.randrange(2 ** 31))
        out.append({"due": begin_s + due[i], "body": body})
    return out


def requests(mix, vocab_size, seed, window_s):
    """One run's requests: those of the ramp (``mix["ramp_s"]`` seconds,
    ids ``ramp<i>``) and then those of the window (ids ``w<i>``), which
    are the run's sample."""
    rng = random.Random(seed)
    ramp_s = mix["ramp_s"]
    return (stretch(mix, vocab_size, rng, "ramp", 0.0, ramp_s)
            + stretch(mix, vocab_size, rng, "w", ramp_s, window_s))
