"""Everything of the benchmark that depends on a model's architecture.

A configuration file picks its family by a top-level ``"family"`` key
(``dense`` when it has none), and ``harness.load_family`` finds the
module ``bench/families/<family>.py`` by that name.  A family module
exports:

* ``layout(m)``: the weight leaves ``(shape, mean, std)``, in the tree the
  served program reads (``bench/weights.py`` makes them from the seed);
* ``hidden(params, tokens, m, weights)`` and ``head(params, weights)``:
  the plain float32 reference's final hidden states of one sequence and
  its output head (logits are ``hidden @ head.T``); with ``weights="fp8"``
  the control, one precision below bf16;
* ``weight_bytes(m)`` and ``kv_bytes_per_token(m)``;
* ``decode_cost(m, ctx_lens, kv_tokens, counters)`` and
  ``prefill_cost(m, suffix, cached, counters)``: the needed ``(flops,
  bytes)`` of one decode step and of one prefill.  ``counters`` holds the
  tracer gauges recorded in the step, by name, so that a model whose
  needed bytes depend on what the step touched (the experts a routed
  model read) can count them;
* ``scope_cost(m, scope, ctx_lens, kv_tokens, counters)``: the needed
  ``(flops, bytes)`` of one named scope's work in a decode step, or None
  for a scope the family does not count.
"""
