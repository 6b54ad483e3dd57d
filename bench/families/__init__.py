"""Model families of the benchmark: one module per family, by path.

A configuration names its family by its ``reference`` key: the family
``bench/families/<reference>.py`` and its plain reference
``bench/references/<reference>.py`` share that name.  ``harness.load_cell``
loads the family module from its path and keeps it on the cell as
``cell.family``; a configuration without one is an error.  A new family
joins the benchmark as new files only: ``configs/<config>.json``,
``families/<reference>.py``, ``references/<reference>.py``,
``traffic/<traffic>.json``, ``limits/<cell>.json`` and any readers under
``metrics/``.

What a family module provides (``dnn_ssl.py`` is the paper's DNN):

* ``experiment_config(config, traffic, seeds)``: the ``ExperimentConfig``
  of a configuration under a traffic mix, ``seeds`` as
  ``harness.seeds_of`` draws them (``data``, ``partition``, ``model``);
* ``warm_eval(exp)``: one call of the held-out eval on the built
  experiment, so that the window's evals load its program from the cache;
* ``EVAL``: ``(module, attribute)`` of the held-out eval call, which the
  harness wraps in its ``eval`` span for the run;
* ``host_state(state)``: the trainer's state on the host, as the
  reference's ``run_steps`` returns it (``params`` and ``accum``);
* ``reference_numbers(cell, seed, first_chunk, first, detail=False,
  **variant)``, ``kernel_check(cell, seed, first_chunk, **variant)`` and
  ``batch_check(cell, info, first_chunk)``: the numbers that decide
  ``correct``, named as in the cell's limits file;
* ``step_flops(config, valid_rows)``: the FLOPs one training step
  requires, ``valid_rows`` the valid rows of each worker's block;
* ``MODEL_SCOPE``: the program's device scope of the family's network;
* ``shrink(cell)``: the cell cut to a size the CPU runs in seconds, for
  ``bench/tests``.

What the harness takes as given of every family (the graph-SSL contract):

* ``Experiment(cfg).build()`` provides ``.pipeline`` (called with
  ``epoch`` and ``n_epochs``, with ``.stream`` holding ``.pad``,
  ``.swaps`` and ``.plan.n_meta``), ``.corpus.n``, ``.eval_data``,
  ``.plan`` and ``.config``; ``.run()`` trains through ``Engine``, whose
  ``_chunk_fn`` and ``_host_chunks`` and the ``place_batch`` of the
  strategy that ``._strategy()`` names the harness wraps;
* every batch has ``valid`` of shape ``(k, P)``;
* the chunk's metrics hold ``loss/total`` and ``loss/graph``;
* the configuration holds ``reference``, ``n_classes``, ``n_workers`` and
  ``base_lr``;
* the traffic holds ``pad_rows``, ``prefetch`` and
  ``repartition_every_n_epochs``.
"""
