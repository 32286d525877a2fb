"""Model families: everything of the benchmark that depends on the block.

A configuration file names its family under ``"family"`` (``"llama"``
where it names none), and ``cells.family(conf)`` imports
``families/<name>.py`` from this package's path; a name that no module
there holds is an error.  A family module gives these functions, each
taking the configuration file's dict ``conf``:

``model_config(conf)``
    The program's ``repro.configs.base.ModelConfig`` of the configuration.
``shapes(conf)``
    The leaf shapes of the parameter tree, nested dicts and lists of int
    tuples in the layout the program serves.  ``weights.make`` draws every
    leaf in one jit on the device; a family may also give
    ``leaf(key, path, shape, dtype)`` to draw its leaves by a rule of its
    own (``weights.leaf`` is the default).
``sender_prefill_flops(conf, prefix)``
    FLOPs the sender's pass over ``prefix`` tokens requires.
``receiver_prefill_flops(conf, query, prefix, layers)``
    FLOPs the receiver's pass over ``query`` tokens requires, where the
    layers whose indices are in ``layers`` (the selection) also see
    ``prefix`` shared keys; the head once.
``decode_row_flops(conf, own, prefix, layers)``
    FLOPs one decode token of one live row requires, with ``own`` keys of
    its own and, at the layers in ``layers``, ``prefix`` shared keys.
``decode_attn_call(conf, layer, keys)``
    (FLOPs, bytes) that one decode-attention call at layer index ``layer``
    requires for live rows that may see ``keys[i]`` keys each.
``Geometry(conf)``
    A hashable object of the sizes the reference needs, passed back to it.
``selection(params, g, context, query, bos, ratio, alpha)``
    The reference's calibration: (the selected layer indices as a sorted
    tuple, the raw per-layer prefix masses).
``hop_logits(params, g, layers, bos, context, query, reply, pad_to,
policy="f32")``
    The float32 reference's logits of one served request at every
    position that chose a reply token; ``policy="fp8"`` is the control,
    the same with every matrix product's operands rounded to float8.

``layer`` and ``layers`` index the model's attention layers in the order
of the program's KV stack, ``0 .. cfg.attn_layer_count - 1``: the same
indices as the program's selection (``Bench.layers``), not indices into
all of its layers.  In a hybrid or recurrent family the two differ, and a
family that caps keys by a layer's window maps the attention index to its
own layer.

The counts are of required work only (valid keys, active experts, no
padding), so that a per-layer metric reads the same whatever implements
it.  A family caps keys by a layer's window or counts experts per token
inside these functions; the metrics pass layer indices, never a count.

A new architecture brings its cell by adding files and editing none:
``families/<name>.py``, ``configs/<config>.json`` naming it,
``workloads/<cell>.json``, a ``traffic/<mix>.json`` where no mix fits,
and its entries in ``BENCHMARK.json``.  A family may import what it shares
with another from that family's module.
"""
