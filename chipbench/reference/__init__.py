"""Plain float32 references, one module per model family.

A configuration file names its family's module under ``"reference"``
(``configs/gpt2-paper.json``: ``"transformer"``), and the harness finds
it as ``reference/<name>.py``.  Such a module imports nothing of the
system under test and provides:

* ``param_shapes(c)``: the parameter tree's leaf shapes, a nested dict;
* ``init(key, c)``: weights from ``key`` in that layout, float32;
* ``loss_and_grad(params, tokens, c, q8, block_rows)``: the mean loss
  and its gradient, every matrix product's operands passed through
  ``q8`` (``controls``), the batch taken ``block_rows`` rows at a time;
* ``train_step_flops(c, seq, batch)``: the FLOPs of one forward and
  backward pass, counting only the matrix work a token really does;
* ``muon_views(c)``: for each leaf that Muon orthogonalizes, by its path
  (``"layers/attn/wq"``), ``(batch, rows)``: the number of leading axes
  that index separate matrices (the layer; the layer and the expert for
  an expert stack), and the axes after them that form each matrix's
  rows (the rest form its columns).

``muon`` (the optimizer) and ``controls`` (the precisions) serve every
family.
"""
