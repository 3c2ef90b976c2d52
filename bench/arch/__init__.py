"""Architectures, found by name.

A configuration ``bench/configs/<config>.json`` names its architecture
with a top-level ``"arch"``; ``bench.spec`` loads ``bench/arch/<arch>.py``
and hands it to the drivers as ``Cell.arch``.  The module is everything
the benchmark knows of the architecture, and provides:

- ``layout(model) -> dict``: the program's parameter tree, each leaf
  ``(shape, kind, scale)`` as ``bench.weights`` draws it ("normal": std
  ``scale``; "gain": ``1 + scale * normal``).
- ``Reference(model, routing, precision)``: the plain float32 forward, with
  ``last_logits(params, tokens, router=None)`` -> (logits [n, vocab],
  router margin [n]) and ``all_logits(params, tokens, router=None)`` ->
  (logits [n, S, vocab], router margin [n, S]).  The margin is the
  smallest gap over the expert layers between a position's k-th and
  (k+1)-th router logit, ``+inf`` without experts; ``router``, when given,
  receives each expert layer's router logits.  ``routing`` is the
  configuration's capacity rule (``None``: dropless); ``precision`` is
  one of ``bench.reference.PRECISIONS`` ("fp8" is the control).
  ``bench.reference`` holds the building blocks to compose it from.
- ``prefill_flops(model, batch, seq_len)`` and
  ``token_flops(model, position)``: the FLOPs a prefill step and a served
  token require (``bench.counts`` has the shared pieces).
- ``fabric_bytes(model, tokens, group_tokens)``, for an architecture with
  experts: the bytes any crossbar must move through one prefill step.

A new architecture is a new file here; nothing else in ``bench/``
changes.
"""
