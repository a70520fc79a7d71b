"""The one general generator of inputs. A traffic mix or a training data
stream is a block of parameters in a workload file; this module turns the
block and ``--seed`` into requests or batches. New traffic is new data, not
new code.

Every seed gets the SAME multiset of sizes in another order: sizes are the
stratified quantiles of the stated distribution (no sampling noise in the
amount of work), and the seed shuffles their order and draws the token ids.
So runs with different seeds do the same work and differ only in what a
real change of inputs changes.

The serving half is standard library only: the load generator is a child
process that must not import JAX (one process per chip), and it imports
this module.
"""

import math
import random
from statistics import NormalDist

_STD_NORMAL = NormalDist()


def seed_stream(seed, *labels):
    """An independent ``random.Random`` for (seed, labels). ``--seed`` may
    exceed 2**31; a string seed hashes all of it."""
    return random.Random("chipbench:" + ":".join(str(x) for x in (seed, ) + labels))


def stratified_lengths(spec, count):
    """``count`` lengths at the mid-quantiles of ``spec``'s distribution:
    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b} or
    {"dist": "uniform", "min": a, "max": b} or {"dist": "fixed", "value": v}."""
    kind = spec["dist"]
    out = []
    for k in range(count):
        u = (k + 0.5) / count
        if kind == "lognormal":
            x = spec["median"] * math.exp(spec["sigma"] * _STD_NORMAL.inv_cdf(u))
        elif kind == "uniform":
            x = spec["min"] + u * (spec["max"] - spec["min"])
        elif kind == "fixed":
            x = spec["value"]
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        lo, hi = spec.get("min", x), spec.get("max", x)
        out.append(int(round(min(max(x, lo), hi))))
    return out


def request_plan(traffic, seed):
    """The (prompt_len, output_len) pairs of a serving mix, in this seed's
    order. ``traffic["pool"]`` pairs are built: prompt lengths and output
    lengths at their own quantiles, paired through a fixed (seed-free)
    shuffle so they are uncorrelated, clipped to ``max_total``; then the
    seed shuffles the order in which clients take them."""
    n = traffic["pool"]
    prompts = stratified_lengths(traffic["prompt_len"], n)
    outputs = stratified_lengths(traffic["output_len"], n)
    random.Random("chipbench:pairing").shuffle(outputs)
    cap = traffic["max_total"]
    pairs = [(p, min(o, cap - p)) for p, o in zip(prompts, outputs)]
    seed_stream(seed, "order").shuffle(pairs)
    return pairs


def prompt_tokens(seed, index, length, vocab_size):
    """Token ids of request ``index``: independent uniform draws, so no two
    prompts share a prefix (the radix cache finds nothing, by design)."""
    rng = seed_stream(seed, "prompt", index)
    return [rng.randrange(vocab_size) for _ in range(length)]


def packed_batches(data, seed, vocab_size, seq_len, batch_size, count):
    """``count`` training batches (batch_size, seq_len) int32 of packed
    synthetic documents: token ids from a Zipf law over the vocabulary (so
    there is a unigram distribution to learn and the loss can fall on fresh
    batches), document lengths log-normal (heavy-tailed), an EOS id between
    documents, documents cut at the sequence end as a packing loader does."""
    import numpy as np
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**63, 0x7ACED]))
    eos = data["eos_token_id"]
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -data["zipf_a"]
    p /= p.sum()
    # a fixed permutation so that frequent ids are not the low ids
    ids_by_rank = np.random.default_rng(0xC0FFEE).permutation(vocab_size)
    total = count * batch_size * seq_len
    tokens = ids_by_rank[rng.choice(vocab_size, size=total, p=p)].astype(np.int32)
    doc = data["doc_len"]
    pos = 0
    while pos < total:
        n = int(round(doc["median"] * math.exp(doc["sigma"] * rng.standard_normal())))
        pos += max(doc.get("min", 1), n)
        if pos < total:
            tokens[pos] = eos
            pos += 1
    return tokens.reshape(count, batch_size, seq_len)
