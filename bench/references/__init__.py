"""Plain references, one module per model family, named by a configuration's
``reference`` key.  A reference imports nothing of the program and takes
nothing it has made.  Its module exports:

* ``init_params(cfg, key)``: the initial parameters, in the program's layout,
  traceable (one jitted call makes them on the device);
* ``seed_key(seed)``: the key of ``init_params`` for any whole number;
* ``Reference(cfg, mesh, batch, seq, precision=, device=)``, whose
  ``run(batches, key, keep_grads=False)`` trains ``len(batches)`` steps from
  ``init_params(cfg, key)`` and returns ``losses``, ``grad_norms`` and
  ``change_norms`` (leaves by path; with ``keep_grads`` also ``grads``),
  ``precision`` being ``"fp32"`` or ``"control"``, one precision below what
  the configuration states;
* optionally ``train_flops_per_token(cfg, seq)``: the model FLOPs of a
  training step per token, which ``mfu`` reads; without it
  ``bench/flops.py`` counts the MLM encoder.
"""
