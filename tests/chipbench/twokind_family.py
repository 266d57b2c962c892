"""A layer family for the tests alone, with two kinds of layer: grouped-query
attention every ``ATTN_EVERY``-th layer (the ``dense`` reference's block)
and Mamba-2 SSD layers otherwise (the ``ssm`` reference's block), on one
residual stream with the dense family's embedding and head. The tests
install it as ``chipbench.reference.twokind``; nothing of the harness
names it."""
from chipbench.reference import dense, ssm

ATTN_EVERY = 3
BLOCKS = {"attn": dense, "ssd": ssm}

rest_shapes = dense.rest_shapes
embed = dense.embed
head = dense.head
norm_eps = dense.norm_eps


def pattern(a: dict) -> list:
    return ["attn" if i % ATTN_EVERY == ATTN_EVERY - 1 else "ssd"
            for i in range(a["num_layers"])]


def layer_shapes(kind: str, a: dict) -> dict:
    return BLOCKS[kind].layer_shapes(kind, a)


def layer(kind: str, p: dict, h, positions, a: dict, P):
    return BLOCKS[kind].layer(kind, p, h, positions, a, P)


def matmul_params(kind: str, a: dict) -> int:
    return BLOCKS[kind].matmul_params(kind, a)


def mixer_flops(kind: str, a: dict, keys) -> float:
    return BLOCKS[kind].mixer_flops(kind, a, keys)


def kv_entries(kind: str, a: dict) -> int:
    return BLOCKS[kind].kv_entries(kind, a)


def decode_state_bytes(kind: str, a: dict, batch: int, cache_bytes: int) -> int:
    return BLOCKS[kind].decode_state_bytes(kind, a, batch, cache_bytes)
