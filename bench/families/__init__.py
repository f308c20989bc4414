"""Architecture families: one module per architecture, holding everything the
benchmark knows of that architecture's mathematics and layout.

A configuration file names its family with ``"family": "<name>"``; a file
without the key is ``dense``.  ``model.family(cfg)`` loads
``bench/families/<name>.py`` by path (an unknown name is an error that lists
the known ones) and checks that it has the whole interface below.  Every
caller reaches the reference, the work counts and the adapter layout only
through it, so a new architecture joins the benchmark as new files: its
``configs/<config>.json`` naming its family, ``families/<family>.py``, a
``traffic/<mix>.json``, the cell's ``limits/<cell>.json`` and its entries in
``BENCHMARK.json``.  A family that builds on another gets it from
``model.load_family(name)``.

``cfg`` is the configuration file as a dict; ``lora`` is ``{target: {"a":
array, "b": array}}``, the benchmark's adapter layout, or None.

- ``program_config(cfg)``: the program's ``ModelConfig`` for the file.
  Raises ``ValueError``, before any device work, for a size that differs
  from the program's registry entry without being in the file's
  ``reduced``, and for a mechanism the family does not compute.
- ``lora_shapes(cfg, rank, targets, lead)``: ``{target: {"a": shape, "b":
  shape}}``, each shape a tuple: the leading dims ``lead``, then the layer
  axis, then the matrix (``a`` is rank x input, ``b`` output x rank).
- ``program_lora(lora)``: the same arrays nested as the program takes them;
  no arithmetic.
- ``forward(cfg, params, tokens, lora, gamma)``: the plain reference's
  logits (batch, seq, vocab) over the real vocabulary, in straightforward
  ``jax.numpy`` that imports nothing of the program and reads the weights
  ``make_params`` made.  With float32 ``params`` and ``lora`` it computes at
  the precision the file states: where ``precision.matmul_operand_bytes``
  is 2, each matrix product rounds its operands to bfloat16 and sums in
  float32, and every other op is float32.  With both cast to bfloat16 it
  computes in bfloat16: the control, which the comparison must fail.
  Callers run it under ``default_matmul_precision("highest")``, so nothing
  is rounded that the family does not round.
- ``loss(cfg, params, tokens, lora, gamma)``: the mean next-token
  cross-entropy over every row and position, in float32 whatever the
  forward's dtype.
- ``cast(tree, dtype)``: the tree with every leaf in ``dtype``.
- ``train_step_flops(cfg, *, sequences, seq, rank, targets)``: the FLOPs of
  one LoRA optimizer step over ``sequences`` rows of ``seq`` tokens.
- ``decode_step_work(cfg, *, positions, tenants, rank, targets)``: ``(FLOPs,
  bytes)`` of one decode step for active requests at absolute
  ``positions``, serving ``tenants`` distinct adapters.

Both counts state the least work the program must do, from the
configuration's own sizes and never from the program: no recomputation, no
masked or padded work, no expert a token is not routed to, and bytes at the
width of the matrix unit's operands.  ``mfu.train`` and ``mfu.serve_decode``
divide the least time of that work by the time measured, so a share of the
roofline cannot pass 100%.

Optional: ``make_params(model, key, mesh=None)``, the base weights in the
program's layout made on the device in one jitted call from ``key``; on a
``mesh`` each chip makes only its share, in the program's placement
(``repro.sharding.rules.params_sharding``), with the same values as
without one.  Without it the family gets ``model.make_params``.
"""
