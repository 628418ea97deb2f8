"""Model families: everything in the benchmark that depends on a model's
layer equations, one module a family.

A configuration file names its family under ``"family"`` (no key means
``"dense"``), and ``spec.family(conf)`` loads
``bench/families/<family>.py``, as ``spec.reader`` loads a metric.  The
harness (``bench/run.py``, ``weights.py``, ``reference.py``,
``counts.py``) holds no shape code of its own: each of its public
names hands the call to the configuration's family.  So a model of a
new family joins the benchmark with new files alone: its family
module, its configuration file and its cell.

A family module defines these functions.  ``conf`` is the parsed
configuration file; each function reads the keys its family needs.

``dims(conf) -> dict``
    The shape numbers of the configuration by short name (layers,
    widths, heads, vocabulary, ...), in whatever names the family's own
    functions read.

``program_config(conf) -> repro.configs.base.ArchConfig``
    The program's configuration named by ``conf["arch"]``, with every
    option the program has set as the file states it (a tied head,
    say).  Import ``repro.configs`` inside the function, so that a test
    can lend the program an architecture.

``check_arch(conf, cfg) -> None``
    Raise ``ValueError`` unless the program's ``cfg`` has the shape the
    file states, naming what differs.

``make(conf, dtype, key) -> params``
    The weights as a tree of arrays of ``dtype``, laid out exactly as
    the program's ``repro.models.lm.init_params`` lays them out (the
    harness compares the two trees by path, shape and dtype at set-up).
    It runs traced inside one ``jax.jit`` with the seed's ``key`` (a
    ``uint32[2]``), so draw every leaf from ``jax.random.split(key, n)``
    in a fixed order: the same seed then gives the same bits.
    ``bench/weights.py``'s ``_normal`` is the usual initializer.

``logits_at(conf, params, tokens, rows, quant) -> np.ndarray``
    The plain float32 reference: logits ``(len(rows), V)`` of the
    sequence ``tokens`` at the positions ``rows``, each seeing the
    tokens up to and including itself.  Straightforward ``jax.numpy``
    at ``Precision.HIGHEST``, reading ``params`` and the file alone and
    nothing of the program.  ``quant`` is ``"none"``, or the control's
    precision (``"fp8"`` below bfloat16, ``"bf16"`` below float32):
    the same arithmetic with every matrix product taken at that
    precision.  ``bench/reference.py`` holds the pieces to build it
    from: ``_mm`` (a product, at ``quant``), ``_norm``, ``_rope``,
    ``_attention`` (causal, in blocks of query rows) and
    ``padded_len``.  Run it a layer at a time so that it fits beside
    the served weights.

``flops(conf, kind, tokens, kv_len) -> float``
``hbm_bytes(conf, kind, tokens, kv_len) -> float``
    The operations, and the bytes moved to or from device memory, that
    one served instruction needs, from the shapes alone: ``kind`` is
    ``"prefill"`` (``tokens`` prompt tokens) or ``"decode"`` (one token
    attending ``kv_len`` keys).  Count what the algorithm needs and no
    more, so that a share of a peak is never overstated, and say in the
    module's docstring what is counted.  A family whose work or bytes
    depend on routing counts them from the shapes too, and states how:
    for sparse experts, say, the operations of the experts each token
    is routed to, and the bytes of the experts an instruction is
    expected to hit when its tokens' choices are spread evenly: of
    ``H`` experts held out of ``E``, with ``k`` a token,
    ``H (1 - (1 - k / E) ** tokens)``.

``dense.py`` is the dense decoder (GQA, RMSNorm or non-parametric
LayerNorm, SwiGLU, tied or untied head) that OLMo-1B and Phi-4-mini
run; read it as the example.
"""
