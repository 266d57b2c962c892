"""Plain float32 references, one module per layer family.

The harness finds a family's module by the ``family`` key of a
configuration's ``arch``: ``chipbench.reference.<family>`` (``family``
below), so a new family is a new file here and no edit elsewhere. The
module imports nothing of the program. A model is a stack of layers, each
of one *kind* (``attn``, ``ssd``, ...); the module names the kind of every
layer and gives, for each kind, its leaves, its forward pass and its work.
It defines:

``pattern(a) -> list[str]``
    The kind of each layer, in order (``a`` is the configuration's
    ``arch``). A uniform family returns one kind ``num_layers`` times.
``layer_shapes(kind, a) -> dict``
    Shape of each leaf of one layer of ``kind``, by its path below the
    kind's prefix (``attn/wqkv``). The model's tree (``common.param_shapes``)
    holds them stacked over the layers of that kind: at ``layers/<leaf>``
    where the pattern has one kind, the program's layout for a uniform
    stack, and at ``layers/<kind>/<leaf>`` where it has several. Layer ``i``
    of a kind is slice ``i`` of each of its stacks, and ``weights.py`` draws
    it from the seed, the path and ``i``.
``rest_shapes(a) -> dict``
    The leaves outside the layers: ``embed``, ``final_norm``, and
    ``lm_head`` where the embeddings are not tied.
``embed(rest, tokens, a)``, ``head(rest, a)``, ``norm_eps(a)``
    The embedding lookup (float32), the (hidden, vocab) output matrix, and
    the final norm's epsilon.
``layer(kind, p, h, positions, a, P)``
    One layer of ``kind`` on ``h`` (B, S, hidden) in float32; ``p`` holds
    the layer's leaves by ``layer_shapes`` path, ``P`` is a
    ``common.Products`` that every matrix product goes through (the
    control rounds its operands).
``matmul_params(kind, a) -> int``
    Weights of one layer of ``kind`` that enter a matrix product.
``mixer_flops(kind, a, keys) -> float``
    Forward FLOPs per token of the layer's sequence mixer over ``keys``
    earlier positions, for the products whose size grows with them (causal
    attention's QK^T and PV); 0 where none does.
``kv_entries(kind, a) -> int``
    Cache entries per position per sequence that a decode step reads back
    (K and V of attention); 0 for a layer with a fixed-size state.
``decode_state_bytes(kind, a, batch, cache_bytes) -> int``
    Bytes of recurrent state one decode step of ``batch`` sequences reads
    and writes, whatever the position (0 for attention); ``cache_bytes`` is
    the size of one element of the served cache's dtype.

``count.py`` sums the work over the pattern and keeps the arithmetic the
families share (the head, the causal mask's half, the factor of three for
training); ``common.py`` runs the layers in the pattern's order.
"""
from __future__ import annotations

import importlib


def family(name: str):
    """The reference module of the family ``name``."""
    return importlib.import_module(f"{__name__}.{name}")
