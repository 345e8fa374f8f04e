"""Plain float32 reference of the served text-to-image workflow.

Straightforward ``jax.numpy`` at highest matmul precision, with no kernel,
no batching across requests and no cache.  It imports nothing of the
program under test: weights are rebuilt here from the same seeds the
program's loaders use (CRC32 of the model id), by this package's own code.

``standins.py`` holds the stand-in text encoder and VAE decoder, which
every architecture shares.  Each backbone architecture has a module of its
own, ``<architecture>.py``, named by the ``architecture`` key of a
configuration file; the harness loads it by path
(``chipbench.harness.architecture``), so a new architecture joins the
benchmark as new files only.  Such a module provides, for a geometry ``g``:

* ``geometry_from_config(cfg)``: the sizes of a configuration file, as a
  hashable object that carries at least what ``standins`` and the stand-in
  counts in ``chipbench/flops.py`` read: ``family``, ``text_dim``,
  ``text_tokens``, ``te_vocab``, ``te_layers``, ``te_heads``, ``te_dtype``,
  ``vae_base``, ``vae_dtype``, ``latent_size`` and ``latent_channels``;
* ``initial_latents(g, seed)``: the noise a request with input ``seed``
  starts from, [1, S, S, C];
* ``sample(g, lat, emb, steps, guidance, start, stop, fp8)``: steps
  ``start`` .. ``stop`` of a ``steps``-step schedule of one request from
  latents ``lat`` under the prompt embedding ``emb`` [1, Tc, text_dim];
  with ``fp8`` the backbone's projections computed in float8 (the control);
* ``rows_per_step(g)``: backbone rows in one request-step (2 where
  classifier-free guidance runs a conditional and an unconditional row,
  1 for a guidance-distilled model);
* ``row_step_flops(g)``: operations of one backbone forward of one row;
* ``attention_calls(g)``: one ``(flops, bytes)`` pair for each call of the
  program's ``mha`` kernel in one row-step, unpadded;
* ``program_fields(g)``: the fields of the program's backbone config
  (``repro.diffusion.config.DiTConfig``) at these sizes, applied over the
  family's published geometry;
* ``REDUCIBLE``: the configuration keys a cell may reduce (list in its
  ``reduced``), each mapped to the ``program_fields`` key it sets.
"""
