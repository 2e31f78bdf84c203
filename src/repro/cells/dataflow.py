"""Programmable RNN dataflow (paper §4.2 / §5.1).

An RNN cell is a DAG of the paper's arithmetic primitives — MVM
(CSB-Engine), element-wise mul/add, sigmoid, tanh (+ relu and 1-x, needed
by Li-GRU/GRU, and a layer normalisation over the hidden units, needed by
the LN-LSTMP of speech transducers). The same graph object serves three
consumers:

1. the **executor** (`cell_apply`) — a small interpreter that traces the
   DAG into a jaxpr, so every cell type runs on one code path (the paper's
   "programmable datapath"). MVM weights may be dense arrays, `PaddedCSB`
   matrices (Pallas CSB kernel), or device-stacked `ShardedCSB` shards
   (mesh-sharded kernel; requires an active `use_rules` mesh with a
   non-trivial "model" axis — see `dist.csb_partition`);
2. the **macro-instruction compiler** (`engine/isa.py`) — list-schedules
   the DAG into VLIW words, reproducing §5.1.2;
3. the **latency model** (`engine/simulator.py`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csb_format import PaddedCSB

KINDS = ("input", "mvm", "bias", "add", "mul",
         "sigmoid", "tanh", "relu", "one_minus", "layernorm")
# variance floor of the layernorm op (Ba et al. 2016 give none)
LN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    kind: str
    inputs: tuple[str, ...] = ()
    shape: tuple[int, int] | None = None  # (out, in) mvm; (out,) bias, LN

    def __post_init__(self):
        assert self.kind in KINDS, self.kind

    @property
    def weights(self) -> dict[str, tuple[int, ...]]:
        """The op's weights by name: an mvm's matrix and a bias carry the
        op's name; a layernorm has a gain ``<name>_g`` and a bias
        ``<name>_b``."""
        if self.kind in ("mvm", "bias"):
            return {self.name: self.shape}
        if self.kind == "layernorm":
            return {f"{self.name}_g": self.shape, f"{self.name}_b": self.shape}
        return {}


@dataclasses.dataclass(frozen=True)
class CellGraph:
    """A cell = DAG + state protocol."""

    name: str
    input_dim: int
    hidden_dim: int
    ops: tuple[Op, ...]
    state_vars: tuple[str, ...]          # e.g. ("h", "c") — fed as inputs
    next_state: dict[str, str]           # state var -> producing op name
    output: str                          # op name of the cell output h_t

    def op(self, name: str) -> Op:
        for o in self.ops:
            if o.name == name:
                return o
        raise KeyError(name)

    @property
    def key(self) -> tuple:
        """What ``cell_apply`` reads of the graph, hashable
        (``next_state`` is a dict)."""
        return (self.name, self.input_dim, self.hidden_dim, self.ops,
                self.state_vars, tuple(sorted(self.next_state.items())),
                self.output)

    @property
    def mvm_ops(self) -> tuple[Op, ...]:
        return tuple(o for o in self.ops if o.kind == "mvm")

    def weight_shapes(self) -> dict[str, tuple[int, ...]]:
        out = {}
        for o in self.ops:
            out.update(o.weights)
        return out

    def param_count(self) -> int:
        return int(sum(np.prod(s) for s in self.weight_shapes().values()))


class GraphBuilder:
    """Tiny DSL for cell graphs."""

    def __init__(self, name: str, input_dim: int, hidden_dim: int):
        self.name = name
        self.input_dim = input_dim
        self.hidden_dim = hidden_dim
        self._ops: list[Op] = []
        self._n = 0

    def _emit(self, kind, inputs=(), shape=None, name=None) -> str:
        name = name or f"{kind}{self._n}"
        self._n += 1
        self._ops.append(Op(name, kind, tuple(inputs), shape))
        return name

    def input(self, name: str) -> str:
        return self._emit("input", name=name)

    def mvm(self, w_name: str, x: str, out_dim: int, in_dim: int) -> str:
        return self._emit("mvm", (x,), (out_dim, in_dim), name=w_name)

    def bias(self, b_name: str, x: str, dim: int) -> str:
        return self._emit("bias", (x,), (dim,), name=b_name)

    def add(self, a: str, b: str) -> str:
        return self._emit("add", (a, b))

    def mul(self, a: str, b: str) -> str:
        return self._emit("mul", (a, b))

    def sigmoid(self, a: str) -> str:
        return self._emit("sigmoid", (a,))

    def tanh(self, a: str) -> str:
        return self._emit("tanh", (a,))

    def relu(self, a: str) -> str:
        return self._emit("relu", (a,))

    def one_minus(self, a: str) -> str:
        return self._emit("one_minus", (a,))

    def layernorm(self, name: str, x: str, dim: int) -> str:
        """(x - mean) / sqrt(var + LN_EPS) * gain + bias over the last
        axis; the weights are ``<name>_g`` and ``<name>_b``."""
        return self._emit("layernorm", (x,), (dim,), name=name)

    def gate(self, prefix: str, x: str, h: str, act: str,
             in_dim: int, hid: int, out_dim: int | None = None) -> str:
        """act(W@x + U@h + b) — the standard RNN gate idiom."""
        out_dim = out_dim or hid
        wx = self.mvm(f"W_{prefix}", x, out_dim, in_dim)
        uh = self.mvm(f"U_{prefix}", h, out_dim, hid)
        s = self.add(wx, uh)
        s = self.bias(f"b_{prefix}", s, out_dim)
        return getattr(self, act)(s)

    def build(self, state_vars, next_state, output) -> CellGraph:
        return CellGraph(
            name=self.name, input_dim=self.input_dim,
            hidden_dim=self.hidden_dim, ops=tuple(self._ops),
            state_vars=tuple(state_vars), next_state=dict(next_state),
            output=output,
        )


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def cell_apply(
    graph: CellGraph,
    params: dict[str, jax.Array | PaddedCSB],
    x: jax.Array,
    state: dict[str, jax.Array],
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One cell step. x: (..., input_dim); state vars: (..., hidden_dim).
    Each mvm runs through ``kernels.ops.matvec``."""
    from repro.kernels.ops import matvec

    env: dict[str, jax.Array] = {"x": x, **state}
    for op in graph.ops:
        if op.kind == "input":
            assert op.name in env, f"missing input {op.name}"
            continue
        a = env[op.inputs[0]]
        if op.kind == "mvm":
            env[op.name] = matvec(params[op.name], a)
        elif op.kind == "bias":
            env[op.name] = a + params[op.name].astype(a.dtype)
        elif op.kind == "add":
            env[op.name] = a + env[op.inputs[1]]
        elif op.kind == "mul":
            env[op.name] = a * env[op.inputs[1]]
        elif op.kind == "sigmoid":
            env[op.name] = jax.nn.sigmoid(a)
        elif op.kind == "tanh":
            env[op.name] = jnp.tanh(a)
        elif op.kind == "relu":
            env[op.name] = jax.nn.relu(a)
        elif op.kind == "one_minus":
            env[op.name] = 1.0 - a
        elif op.kind == "layernorm":
            env[op.name] = layernorm(a, params[f"{op.name}_g"],
                                     params[f"{op.name}_b"])
        else:  # pragma: no cover
            raise ValueError(op.kind)
    new_state = {k: env[v] for k, v in graph.next_state.items()}
    return env[graph.output], new_state


def layernorm(x: jax.Array, gain: jax.Array, bias: jax.Array) -> jax.Array:
    """Layer normalisation over the last axis (Ba et al. 2016)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + LN_EPS) * gain.astype(x.dtype)
            + bias.astype(x.dtype))


def init_state(graph: CellGraph, batch_shape: tuple[int, ...],
               dtype=jnp.float32) -> dict[str, jax.Array]:
    dims = {"h": graph.hidden_dim, "c": graph.hidden_dim}
    # LSTMP: h is the projected (output) dim
    out_op = graph.op(graph.next_state.get("h", graph.output))
    if out_op.kind == "mvm" and out_op.shape is not None:
        dims["h"] = out_op.shape[0]
    return {
        k: jnp.zeros((*batch_shape, dims.get(k, graph.hidden_dim)), dtype)
        for k in graph.state_vars
    }


def init_params(graph: CellGraph, key: jax.Array,
                dtype=jnp.float32, scale: float | None = None) -> dict:
    params = {}
    gains = {f"{o.name}_g" for o in graph.ops if o.kind == "layernorm"}
    for name, shape in graph.weight_shapes().items():
        key, sub = jax.random.split(key)
        if name in gains:
            params[name] = jnp.ones(shape, dtype)
        elif len(shape) == 1:
            params[name] = jnp.zeros(shape, dtype)
        else:
            s = scale or (1.0 / np.sqrt(shape[1]))
            params[name] = (jax.random.normal(sub, shape) * s).astype(dtype)
    return params


def rnn_scan(
    graph: CellGraph,
    params: dict,
    xs: jax.Array,                      # (T, ..., input_dim)
    state: dict[str, jax.Array] | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Run the cell over a sequence with lax.scan (time-major)."""
    if state is None:
        state = init_state(graph, xs.shape[1:-1], xs.dtype)

    def step(carry, x_t):
        y, new = cell_apply(graph, params, x_t, carry)
        return new, y

    final, ys = jax.lax.scan(step, state, xs)
    return ys, final
