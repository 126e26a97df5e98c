"""Model modules: everything the benchmark knows of one model family.

A configuration file names its module with ``"model": "<name>"``;
``spec.load_cell`` loads ``bench/models/<name>.py`` by path as
``Cell.model``.  A missing module is an error; there is no default.  Each
module exposes:

``program_config(config) -> ModelConfig``
    The program's configuration for the file: the registry entry it names,
    given the options the program has, then checked key by key against the
    file's published numbers.  The program must store the weights at least
    as exactly as the file's ``torch_dtype`` holds them.

``program_params(key, config, param_dtype)``
    The seed's weights in the program's parameter tree and storage dtype,
    traceable (``program.make_params`` jits it with the program's
    shardings and checks the tree against ``T.init_params``).

``Reference(config, key, max_seq)``
    The plain float32 forward, importing nothing of the program, with
    ``gaps(seqs, next_tokens, alt_tokens=None)``: per sequence the gap of
    each position's ``next_tokens`` (and ``alt_tokens``) logit below the
    best, and ``argmax(seqs, quant)``: the token each position puts first
    at the control's precision (``quant="fp8"``).
    ``reference.served_gaps`` drives both.

``step_counts(config, cfg, params, kv_itemsize)``
    An object with ``token_flops(pos)`` and ``token_kv_bytes(pos)`` (one
    token at position ``pos``, arrays of positions alike) and
    ``bucket_param_bytes(b)`` (a compiled burst of ``b`` steps);
    ``counts.burst_counts`` sums them over a dispatched burst.

``small(config) -> config``
    The file cut to the registry's smoke size, for the CPU tests: the
    weights, the reference and the counts follow the cut numbers, and
    ``program_config`` of the result is the smoke entry.

``key`` is ``weights.root_key(seed)``.  A module imports nothing of the
program (``repro``) at its top, so that its weights and its ``Reference``
load and run without the program; ``test_bench_check.py`` holds every
module to that.  A new model family is a new module and configuration files
naming it; no file here changes.
"""
