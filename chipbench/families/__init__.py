"""Model families: everything in the benchmark that depends on the
architecture, one module per `model_type`, found by name as
`chipbench/families/<model_type>.py` (`harness.load_family`).  The rest
of `chipbench/` holds the SplitFT mechanics alone.

A family module gives:

* `dims(cfg)`: the sizes, read from the configuration file's published
  keys.  The harness reads `layers` (their number), `vocab`, `head_macs`
  (LM head multiply-adds per token) and `layer`, one entry per layer:
  `group` and `index` (the program's weight group and the layer's row in
  its stack), `kind`, `attn` (`heads`, `kv_heads`, `qk_dim`, `v_dim`,
  `window`, 0 for global, and `scale`), `targets` ({LoRA target:
  (d_in, d_out)}, in the order their keys are folded), and `macs`, the
  base matmuls' multiply-adds per token (of a layer of routed experts:
  the k active of E, as `flops.py` counts model FLOPs; such a layer also
  gives `experts`, {"total": E, "active": k, "held": the share of E on
  one chip}, for the readers of per-chip expert kernels).  Anything else
  in it is the family's own.
* `tiny(cfg)`: the configuration at the sizes the CPU tests run.
* `program_sizes(arch)`: the program's registry entry under the keys of
  `dims`, which `harness.check_program_arch` compares.
* `base_shapes(dims)`: {path: shape} of the base weights in the
  program's layout, group names included; `draw(key, path, shape,
  dtype)`: how a leaf is drawn from the seed.
* The model half of the reference: `embed(params, tokens, dims)`,
  `block(x, params, l, ads, dims)` (layer l with its adapters, {target:
  (A, B, scale)}) and `head(params, x, dims)` (final norm and logits).
"""
