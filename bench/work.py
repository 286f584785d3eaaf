"""Work counts from shapes: the model's multiply-adds and each kernel call's
least operations and bytes.

Everything here is computed from a configuration file's sizes, the batch
bucket and the per-layer modes of a compiled step, never read from the
program. A roofline share is the least time these counts allow (the larger
of operations over the peak rate and bytes over the peak bandwidth) divided
by the kernel's measured device time, so every count is a lower bound: the
true (unpadded) extents, and for the diff kernel the bytes that any
implementation of the call moves plus operations only for tiles the run
shows were non-zero.
"""
from __future__ import annotations

import dataclasses

TILE = 128  # the Pallas kernels' (bm, bk) tile edge


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a DiT configuration that fix every matmul's shape."""
    d: int  # hidden size
    layers: int
    heads: int
    tokens: int
    ff: int  # MLP width
    patch_dim: int  # patch * patch * in_channels (input of the patch embedding)
    out_dim: int  # patch * patch * output channels

    @property
    def head_dim(self) -> int:
        return self.d // self.heads


def dims(config: dict) -> Dims:
    d = config["hidden_size"]
    p = config["patch_size"]
    c = config["in_channels"]
    out_c = 2 * c if config.get("learn_sigma") else c
    return Dims(d=d, layers=config["depth"], heads=config["num_heads"],
                tokens=(config["input_size"] // p) ** 2,
                ff=int(config["mlp_ratio"] * d), patch_dim=p * p * c,
                out_dim=p * p * out_c)


def dit_macs(config: dict) -> int:
    """Multiply-adds of one DiT forward for one image: the count behind the
    DiT paper's Table 4 (118.6 G for DiT-XL/2 at 256x256)."""
    g = dims(config)
    t, d = g.tokens, g.d
    per_layer = (4 * t * d * d  # q, k, v, o projections
                 + 2 * t * t * d  # q k^T and p v
                 + 2 * t * d * g.ff  # MLP
                 + d * 6 * d)  # adaLN modulation (per image, not per token)
    embed = t * g.patch_dim * d + 256 * d + d * d  # patches, timestep MLP
    final = d * 2 * d + t * d * g.out_dim
    return g.layers * per_layer + embed + final


@dataclasses.dataclass(frozen=True)
class Call:
    """One kernel call: x (m, k) times W (k, n); ``count`` identical calls."""
    kernel: str  # "int8_matmul" | "ditto_diff_matmul"
    layer: str
    m: int
    k: int
    n: int
    count: int = 1
    y_prev: bool = False  # diff kernel reads an int32 (m, n) y_prev

    def ops(self) -> float:
        return 2.0 * self.m * self.k * self.n * self.count

    def bytes(self) -> float:
        """Least bytes: int8 operands in, int32 result out. The diff
        kernel's weight is not counted, since a call whose tiles are all
        zero needs none of it."""
        if self.kernel == "int8_matmul":
            b = self.m * self.k + self.k * self.n + 4 * self.m * self.n
        else:
            b = 2 * self.m * self.k + 4 * self.m * self.n * (2 if self.y_prev else 1)
        return float(b * self.count)

    def least_tile_ops(self) -> float:
        """Operations one non-zero (TILE x TILE) tile of x costs at least: the
        smallest true tile extent times the output width."""
        tm = self.m % TILE or TILE  # an edge tile holds the remainder
        tk = self.k % TILE or TILE
        return 2.0 * tm * tk * self.n


def layer_calls(g: Dims, bucket: int, layer: str, mode: str) -> list[Call]:
    """Kernel calls of one layer in one compiled step (modes: act / diff;
    spatial runs the act kernel)."""
    op = layer.split(".")[-1]
    t, d, hd, bh = g.tokens, g.d, g.head_dim, bucket * g.heads
    diff = mode == "diff"
    if op in ("qk", "pv"):
        # per (sample, head): a (m, kk) times b^T; act runs one int8 call,
        # diff runs the two sub-operations of the attention identity
        m, kk, n = (t, hd, t) if op == "qk" else (t, t, hd)
        if not diff:
            return [Call("int8_matmul", layer, m, kk, n, bh)]
        return [Call("ditto_diff_matmul", layer, n, kk, m, bh),  # x = b rows, W = a
                Call("ditto_diff_matmul", layer, m, kk, n, bh)]  # x = a rows, W = b_prev
    rows = bucket if op == "mod" else bucket * t
    k, n = {"mod": (d, 6 * d), "wq": (d, d), "wk": (d, d), "wv": (d, d),
            "wo": (d, d), "wi": (d, g.ff), "wd": (g.ff, d),
            "out": (d, g.out_dim)}[op]
    if diff:
        return [Call("ditto_diff_matmul", layer, rows, k, n, 1, y_prev=True)]
    return [Call("int8_matmul", layer, rows, k, n)]


def step_calls(config: dict, bucket: int, modes: dict[str, str]) -> list[Call]:
    """Every kernel call of one compiled step, given its per-layer modes."""
    g = dims(config)
    calls: list[Call] = []
    for layer, mode in sorted(modes.items()):
        calls.extend(layer_calls(g, bucket, layer, mode))
    return calls


def diff_tile_ops(config: dict, bucket: int, layer: str) -> float:
    """Least operations per non-zero tile of a diff layer (the smaller of its
    calls' tile costs, since the program's histogram sums both of an
    attention layer's sub-operations)."""
    calls = layer_calls(dims(config), bucket, layer, "diff")
    return min(c.least_tile_ops() for c in calls)
