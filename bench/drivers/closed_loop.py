"""Closed loop, one client: it submits the next request when the
previous verdict returns, and polls the request round by round. Request
``i`` is ``run.request(config, traffic, seed, i)``, whose lanes are the
traffic's ``generators``, each with a seed of its own drawn from the
run's seed.

Traffic keys, all read here:

- ``driver``: ``"closed_loop"``;
- ``generators``: one or more distinct generator names, one lane each,
  every one with a plain reference in ``bench/generators/<name>.py``;
- ``why``: one line on what the mix is for.

Any other key, or a value outside these, is refused before a run.

The window closes with the first round that returns after the deadline:
a request that completes in that round is stitched and counts; one
still in flight counts the words of its finished tests.
"""
from bench import reference

KEYS = {"driver", "generators", "why"}


def validate(traffic: dict) -> None:
    extra = sorted(set(traffic) - KEYS)
    if extra:
        raise ValueError(f"closed_loop traffic does not read {extra}")
    gens = traffic.get("generators")
    if (not isinstance(gens, list) or not gens
            or len(set(gens)) != len(gens)
            or not all(isinstance(g, str) for g in gens)):
        raise ValueError("closed_loop traffic needs distinct generator "
                         f"names, got {gens!r}")
    unknown = sorted(set(gens) - set(reference.generators()))
    if unknown:
        raise ValueError(f"generators {unknown} have no plain reference; "
                         f"known: {reference.generators()}")


def drive(w) -> None:
    while True:
        r = w.submit()
        while r.pending:
            w.poll(r)
            if w.expired():
                break
        if r.pending:
            w.close(r)
            return
        w.finish(r)
        if w.expired():
            return
