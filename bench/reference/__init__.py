"""Plain references the correctness check compares the program with.

A configuration file names its reference module by path (``"reference":
"bench/reference/dense_gqa.py"``); ``bench/run.py`` loads it and the
harness knows no architecture of its own. A new architecture is a new
module here and a configuration that names it. The module provides:

  Spec.from_model(model)   what the forward needs from the configuration
                           file's ``model`` object (hashable: a static
                           argument of ``logits_at``);
  logits_at(spec, w, tokens, positions, mode)
                           float32 logits (n, vocab) at ``positions`` of
                           one causal forward over ``tokens`` (S,);
                           ``mode`` is ``"f32"`` or a control
                           (``check.CONTROLS``: ``"int8"``, ``"fp8"``);
  Q_CHUNK                  S is a multiple of it, or below it;
  plain(tree)              the program's parameter tree under the names
                           ``logits_at`` reads;
  counts(model)            an object whose methods count the work:
                           ``decode_flops(contexts)``,
                           ``prefill_flops(n)``,
                           ``paged_attn_bytes(contexts)``,
                           ``paged_attn_flops(contexts)``;
  WEIGHT_RULES (optional)  leaf name -> ``rule(key, path, shape)``, the
                           float32 values of a parameter leaf that
                           ``bench/weights.py``'s own rules do not cover.

A reference imports nothing of the program.
"""
