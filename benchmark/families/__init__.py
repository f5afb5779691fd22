"""One file per model family: everything the harness asks about a model's block, it asks here.

A configuration file names its ``family``; ``common.load_family(name)`` imports
``benchmark/families/<name>.py``, in the driver and again, by that name, in the worker that holds
the chip. A family is the only place in ``benchmark/`` that names the program's model module, and
the only place that holds layer equations. What a family file defines (``NAMES`` below; ``c`` is
a configuration file's content under its published keys, ``cfg`` the program's config object):

    program_config(c, max_seq_len, **extra) -> cfg   ``extra``: the configuration's ``training``
                                                     block, or ``remat=False`` for serving
    init_params(cfg, key) -> params                  weights from a seed, called under ``jax.jit``
    loss_fn(params, batch, config=cfg, mesh=mesh)    the training loss the program's step minimises
    param_logical_axes(cfg)                          its sharding axes, for ``make_train_step``
    reference_logprobs(params, tokens, c, start, stop) -> [stop - start, vocab]
                                                     the plain reference: float32, ``highest``
                                                     precision, no cache, no kernel, one layer's
                                                     weights cast at a time; written from the
                                                     published description of the block
    rehearsal(c) -> c at toy sizes                   for ``--rehearse``: wiring only
    train_flops_per_token(c, seq) -> float           FLOPs a trained token requires (no recompute)
    kernels_expected(c) -> {name: marker}            kernels that must be in the lowered step
                                                     program on the TPU, by the text that marks them
"""

NAMES = ("program_config", "init_params", "loss_fn", "param_logical_axes", "reference_logprobs",
         "rehearsal", "train_flops_per_token", "kernels_expected")
