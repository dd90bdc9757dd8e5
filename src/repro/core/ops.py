"""The instrumented imperative op namespace (Terra's "DL operations").

Every function here is a *DL operation* in the paper's sense: when executed
under a Terra engine it is recorded into the trace (tracing phase) or
validated against the TraceGraph (co-execution phase); with no engine active
it simply executes eagerly with jax.numpy — that is the plain imperative
baseline the paper compares against.

Argument convention
-------------------
* positional arguments are tensors: TerraTensor | Variable-read | jax/numpy
  array (becomes a *feed point*) | Python scalar (becomes a baked constant —
  exactly TF's constant-capture semantics, so programs that mutate such
  values exhibit the paper's Figure-1c behaviour and are handled by Terra
  through trace branching).
* keyword arguments are op *attributes* (part of node equality, Appendix A).

Autodiff: ``GradientTape`` replays the recorded trace backwards, emitting one
``<op>.vjp`` operation per forward operation — so the backward pass lands in
the TraceGraph exactly like LazyTensor/PyTorch-XLA backward traces.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.tensor import TerraTensor, Variable, current_engine
from repro.core.trace import Aval, Ref, VarRef, user_location


# --------------------------------------------------------------------------
# Op registry
# --------------------------------------------------------------------------

@dataclasses.dataclass
class OpDef:
    name: str
    impl: Callable                 # pure jax fn: (*tensors, **attrs) -> array | tuple


OPS: Dict[str, OpDef] = {}


@dataclasses.dataclass(frozen=True)
class Const:
    """A Python scalar captured as a baked constant input slot."""
    value: Any

    def __hash__(self):
        return hash((type(self.value).__name__, self.value))


def def_op(name: str, impl: Callable) -> Callable:
    """Register ``impl`` and return the user-facing instrumented function."""
    OPS[name] = OpDef(name, impl)

    def op_fn(*tensor_args, **attrs):
        return _call_op(name, tensor_args, attrs)

    op_fn.__name__ = name
    return op_fn


def op_impl(name: str) -> Callable:
    return OPS[name].impl


# --------------------------------------------------------------------------
# Dispatch
# --------------------------------------------------------------------------

def _canon_attrs(attrs: dict) -> Tuple[Tuple[str, Any], ...]:
    def canon(v):
        if isinstance(v, list):
            return tuple(canon(x) for x in v)
        if isinstance(v, np.dtype):
            return str(v)
        return v
    return tuple(sorted((k, canon(v)) for k, v in attrs.items()))


def _classify_arg(a):
    """-> ('tensor', TerraTensor) | ('const', scalar) | ('feed', np/jax array)."""
    if isinstance(a, TerraTensor):
        return ("tensor", a)
    if isinstance(a, Variable):
        # implicit read
        return ("tensor", a.read()) if current_engine() is not None else ("feed", a.value())
    if isinstance(a, (bool, int, float)) or a is None:
        return ("const", a)
    if isinstance(a, (np.ndarray, np.generic)):
        return ("feed", np.asarray(a))
    if type(a).__module__.startswith("jax") or hasattr(a, "__jax_array__"):
        return ("feed", a)
    raise TypeError(f"unsupported op argument of type {type(a)}")


def _call_op(name: str, tensor_args, attrs):
    eng = current_engine()
    attrs_t = _canon_attrs(attrs)
    args = [_classify_arg(a) for a in tensor_args]
    if eng is None:
        # plain imperative execution — unwrap and run
        vals = []
        for kind, a in args:
            if kind == "tensor":
                vals.append(a._eager if a._eager is not None else a.value())
            elif kind == "const":
                vals.append(a.value if isinstance(a, Const) else a)
            else:
                vals.append(a)
        out = OPS[name].impl(*vals, **dict(attrs_t))
        return _wrap_eager(out)
    loc = user_location(skip_files=getattr(eng, "skip_files", ()))
    return eng.record_op(name, args, attrs_t, loc)


def _wrap_eager(out):
    if isinstance(out, tuple):
        return tuple(TerraTensor(None, Aval.of(o), eager=o) for o in out)
    return TerraTensor(None, Aval.of(out), eager=out)


# --------------------------------------------------------------------------
# Generic VJP ops: one `<name>.vjp` op per forward op
# --------------------------------------------------------------------------

def get_vjp_op_name(fwd_name: str) -> str:
    name = fwd_name + ".vjp"
    if name not in OPS:
        fwd_impl = OPS[fwd_name].impl

        def vjp_impl(*args, _n_out: int, _n_in: int, **attrs):
            cts = args[:_n_out]
            inputs = args[_n_out:_n_out + _n_in]

            def primal(*ins):
                return fwd_impl(*ins, **attrs)

            _, vjp_fn = jax.vjp(primal, *inputs)
            ct = cts[0] if _n_out == 1 else tuple(cts)
            outs = vjp_fn(ct)
            return tuple(outs) if len(outs) > 1 else outs[0]

        OPS[name] = OpDef(name, vjp_impl)
    return name


# --------------------------------------------------------------------------
# GradientTape (TF-style; backward ops are recorded as Terra ops)
# --------------------------------------------------------------------------

class GradientTape:
    def __init__(self):
        self._start = None
        self._engine = None

    def __enter__(self):
        eng = current_engine()
        if eng is None:
            raise RuntimeError("GradientTape requires an active Terra engine "
                               "(use terra.imperative()/Terra runtime)")
        self._engine = eng
        self._start = eng.tape_mark()
        return self

    def __exit__(self, *exc):
        return False

    def gradient(self, loss: TerraTensor, sources):
        """Emit the backward trace for ``loss`` w.r.t. ``sources``.

        ``sources`` is a list of Variables or TerraTensors.  Returns a list
        of TerraTensors (cotangents), zeros where unconnected.
        """
        eng = self._engine
        entries, tensors_of = eng.tape_slice(self._start)
        if not isinstance(loss.ref, Ref):
            raise ValueError("loss must be produced by a recorded op")

        source_refs = []
        for s in sources:
            if isinstance(s, Variable):
                source_refs.append(eng.variable_read_ref(s))
            else:
                source_refs.append(s.ref)

        ct: Dict[Any, TerraTensor] = {loss.ref: ones_like(loss)}

        # entries are in execution (topological) order — walk backward
        for idx in range(len(entries) - 1, -1, -1):
            ordinal, entry = entries[idx]
            out_cts = [ct.get(Ref(ordinal, i)) for i in range(len(entry.out_avals))]
            if all(c is None for c in out_cts):
                continue
            if entry.op_name in _NONDIFF_OPS:
                continue
            outs = tensors_of(ordinal)
            filled = [c if c is not None else zeros_like(outs[i])
                      for i, c in enumerate(out_cts)]
            in_tensors = eng.tensors_for_input_slots(ordinal, entry)
            vjp_name = get_vjp_op_name(entry.op_name)
            grads = _call_op(
                vjp_name,
                tuple(filled) + tuple(in_tensors),
                dict(entry.attrs) | {"_n_out": len(entry.out_avals),
                                     "_n_in": len(in_tensors)},
            )
            if not isinstance(grads, tuple):
                grads = (grads,)
            for slot, g in zip(entry.input_refs, grads):
                if isinstance(slot, (Ref, VarRef)) and _is_float(g.aval.dtype):
                    prev = ct.get(slot)
                    ct[slot] = g if prev is None else add(prev, g)

        results = []
        for s, r in zip(sources, source_refs):
            g = ct.get(r)
            if g is None:
                ref_t = s.read() if isinstance(s, Variable) else s
                g = zeros_like(ref_t)
            results.append(g)
        return results


def _is_float(dtype: str) -> bool:
    return np.issubdtype(np.dtype(dtype), np.floating)


_NONDIFF_OPS = {"greater", "less", "greater_equal", "less_equal", "equal",
                "argmax", "argmin", "stop_gradient", "iota", "one_hot_int"}


# --------------------------------------------------------------------------
# Composite ops: register any pure-JAX function as a single DL operation
# --------------------------------------------------------------------------

def terra_op(fn: Callable = None, *, name: str = None, nondiff: bool = False):
    """Decorator: wrap a pure JAX function as one Terra DL operation.

    This is the framework-scale granularity: e.g. a fully fused, pjit-ready
    ``train_step`` becomes a single node in the TraceGraph (see DESIGN.md §2,
    row "TF ops = graph nodes").
    """
    def deco(f):
        opname = name or f"composite.{f.__module__}.{f.__qualname__}"
        op = def_op(opname, f)
        if nondiff:
            _NONDIFF_OPS.add(opname)
        functools.update_wrapper(op, f)
        return op
    return deco(fn) if fn is not None else deco


# --------------------------------------------------------------------------
# RNG plumbing (random ops take a key feed so graphs stay iteration-stable)
# --------------------------------------------------------------------------

_eager_key = [jax.random.PRNGKey(0)]
_eager_key_lock = threading.Lock()


def _next_key():
    eng = current_engine()
    if eng is not None:
        return eng.next_rng_key()
    with _eager_key_lock:
        _eager_key[0], k = jax.random.split(_eager_key[0])
    return k


# --------------------------------------------------------------------------
# The op set
# --------------------------------------------------------------------------

def _idx_encode(idx):
    def enc(i):
        if isinstance(i, slice):
            return ("slice", i.start, i.stop, i.step)
        if i is Ellipsis:
            return ("ellipsis",)
        if i is None:
            return ("newaxis",)
        if isinstance(i, int):
            return ("int", i)
        raise TypeError(f"only static indices supported, got {type(i)}")
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(enc(i) for i in idx)


def _idx_decode(enc):
    out = []
    for e in enc:
        if e[0] == "slice":
            out.append(slice(e[1], e[2], e[3]))
        elif e[0] == "ellipsis":
            out.append(Ellipsis)
        elif e[0] == "newaxis":
            out.append(None)
        else:
            out.append(e[1])
    return tuple(out)


identity      = def_op("identity", lambda a: jnp.asarray(a))
add           = def_op("add", lambda a, b: jnp.add(a, b))
sub           = def_op("sub", lambda a, b: jnp.subtract(a, b))
mul           = def_op("mul", lambda a, b: jnp.multiply(a, b))
div           = def_op("div", lambda a, b: jnp.divide(a, b))
power         = def_op("power", lambda a, b: jnp.power(a, b))
neg           = def_op("neg", lambda a: jnp.negative(a))
exp           = def_op("exp", lambda a: jnp.exp(a))
log           = def_op("log", lambda a: jnp.log(a))
sqrt          = def_op("sqrt", lambda a: jnp.sqrt(a))
rsqrt         = def_op("rsqrt", lambda a: jax.lax.rsqrt(a))
square        = def_op("square", lambda a: jnp.square(a))
tanh          = def_op("tanh", lambda a: jnp.tanh(a))
sigmoid       = def_op("sigmoid", lambda a: jax.nn.sigmoid(a))
relu          = def_op("relu", lambda a: jax.nn.relu(a))
gelu          = def_op("gelu", lambda a: jax.nn.gelu(a))
silu          = def_op("silu", lambda a: jax.nn.silu(a))
softmax       = def_op("softmax", lambda a, *, axis=-1: jax.nn.softmax(a, axis=axis))
log_softmax   = def_op("log_softmax", lambda a, *, axis=-1: jax.nn.log_softmax(a, axis=axis))
matmul        = def_op("matmul", lambda a, b: jnp.matmul(a, b))
einsum        = def_op("einsum", lambda *xs, expr: jnp.einsum(expr, *xs))
reshape       = def_op("reshape", lambda a, *, new_shape: jnp.reshape(a, new_shape))
transpose     = def_op("transpose", lambda a, *, axes=None: jnp.transpose(a, axes))
_getitem_raw  = def_op("getitem", lambda a, *, idx: a[_idx_decode(idx)])
concat        = def_op("concat", lambda *xs, axis=0: jnp.concatenate(xs, axis=axis))
stack_op      = def_op("stack", lambda *xs, axis=0: jnp.stack(xs, axis=axis))
reduce_sum    = def_op("reduce_sum", lambda a, *, axis=None, keepdims=False: jnp.sum(a, axis=axis, keepdims=keepdims))
reduce_mean   = def_op("reduce_mean", lambda a, *, axis=None, keepdims=False: jnp.mean(a, axis=axis, keepdims=keepdims))
reduce_max    = def_op("reduce_max", lambda a, *, axis=None, keepdims=False: jnp.max(a, axis=axis, keepdims=keepdims))
argmax        = def_op("argmax", lambda a, *, axis=-1: jnp.argmax(a, axis=axis))
greater       = def_op("greater", lambda a, b: jnp.greater(a, b))
less          = def_op("less", lambda a, b: jnp.less(a, b))
greater_equal = def_op("greater_equal", lambda a, b: jnp.greater_equal(a, b))
less_equal    = def_op("less_equal", lambda a, b: jnp.less_equal(a, b))
equal         = def_op("equal", lambda a, b: jnp.equal(a, b))
where         = def_op("where", lambda c, a, b: jnp.where(c, a, b))
cast          = def_op("cast", lambda a, *, dtype: a.astype(dtype))
stop_gradient = def_op("stop_gradient", lambda a: jax.lax.stop_gradient(a))
zeros_like    = def_op("zeros_like", lambda a: jnp.zeros_like(a))
ones_like     = def_op("ones_like", lambda a: jnp.ones_like(a))
abs_op        = def_op("abs", lambda a: jnp.abs(a))
maximum       = def_op("maximum", lambda a, b: jnp.maximum(a, b))
minimum       = def_op("minimum", lambda a, b: jnp.minimum(a, b))
clip          = def_op("clip", lambda a, *, lo, hi: jnp.clip(a, lo, hi))
embedding     = def_op("embedding", lambda table, ids: jnp.take(table, ids, axis=0))
one_hot       = def_op("one_hot", lambda ids, *, depth, dtype="float32": jax.nn.one_hot(ids, depth, dtype=dtype))
layer_norm    = def_op(
    "layer_norm",
    lambda x, g, b, *, eps=1e-5: g * (x - jnp.mean(x, -1, keepdims=True))
    * jax.lax.rsqrt(jnp.var(x, -1, keepdims=True) + eps) + b)
rms_norm      = def_op(
    "rms_norm",
    lambda x, g, *, eps=1e-6: g * x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps))
conv2d        = def_op(
    "conv2d",
    lambda x, w, *, stride=1, padding="SAME": jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
max_pool2d    = def_op(
    "max_pool2d",
    lambda x, *, window=2, stride=2: jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID"))
avg_pool2d    = def_op(
    "avg_pool2d",
    lambda x, *, window=2, stride=2: jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, window, window, 1),
        (1, stride, stride, 1), "VALID") / (window * window))
resize_nearest = def_op(
    "resize_nearest",
    lambda x, *, factor=2: jnp.repeat(jnp.repeat(x, factor, axis=1), factor, axis=2))

_dropout_raw = def_op(
    "dropout",
    lambda x, key, *, rate: jnp.where(
        jax.random.bernoulli(key, 1.0 - rate, x.shape),
        x / (1.0 - rate), jnp.zeros_like(x)) if rate > 0.0 else x)

_random_normal_raw = def_op(
    "random_normal",
    lambda key, *, shape, dtype="float32": jax.random.normal(key, shape, dtype=dtype))

_random_uniform_raw = def_op(
    "random_uniform",
    lambda key, *, shape, dtype="float32": jax.random.uniform(key, shape, dtype=dtype))

softmax_xent = def_op(
    "softmax_xent",
    lambda logits, labels: -jnp.mean(
        jnp.sum(jax.nn.log_softmax(logits, -1)
                * jax.nn.one_hot(labels, logits.shape[-1]), -1)))


def getitem(a, *, idx):
    return _getitem_raw(a, idx=_idx_encode(idx))


def dropout(x, rate: float):
    """Dropout with the rate captured as a baked constant (TF semantics).

    ``rate`` changing across iterations (e.g. via Python object mutation,
    Figure 1c) produces a trace branch that Terra handles transparently.
    """
    return _dropout_raw(x, _next_key(), rate=float(rate))


def random_normal(shape, dtype="float32"):
    return _random_normal_raw(_next_key(), shape=tuple(shape), dtype=dtype)


def random_uniform(shape, dtype="float32"):
    return _random_uniform_raw(_next_key(), shape=tuple(shape), dtype=dtype)


def mean_squared_error(pred, target):
    return reduce_mean(square(sub(pred, target)))


def sparse_softmax_xent(logits, labels):
    return softmax_xent(logits, labels)
