"""What a test module pays once: a tiny engine a configuration, and a
compiled program a call.

What a tiny `LLMEngine` costs on the CPU is `_build_compute` (the model, the
parameters, tracing and lowering its programs): 5-18 s a preset. What a case
needs of a NEW engine is an empty pool, a full allocator and zeroed
counters, and those are three statements. So a module asks `tiny_engine` for
the engine of a configuration, which is built on the first call and kept
until the module ends (`close_all`, called by conftest.py before JAX's
caches go), and `renewed` makes an idle engine new again without building
anything.

A test that asserts on what a FIRST use builds (`programs_built_total`,
`engine.program_built` records, `compute.programs`) keeps an engine of its
own: a shared engine has its programs already, and the assertion would pass
for the wrong reason.

The other thing paid again and again is dispatch an op at a time: a jnp
reference, a kernel's wrapper or `model.apply` called as it stands traces,
lowers and compiles every op of every new shape by itself (49 programs for
one gradient through `flash_attention` and its reference, hundreds for a
family's forward). `jitted` makes the call one program."""

import atexit
import contextlib
import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, PageAllocator
from ray_tpu.serve.llm.stage import init_params

# what the files' own builders agreed on before there was one
DEFAULTS = dict(dtype="float32", page_size=16, num_pages=64,
                max_model_len=256, max_batch=4,
                prefill_buckets=(16, 32, 64, 128))

_open: Dict[tuple, LLMEngine] = {}


def jitted(fn):
    """`fn` as ONE compiled program a call. Arguments that are trees of
    arrays are traced; every other (a flag, a length, a configuration, a
    model) is closed over, as the eager call had it. `fn` itself stays at
    `.__wrapped__`, for a test that reads its jaxpr."""
    def traced(value) -> bool:
        leaves = jax.tree.leaves(value)
        return bool(leaves) and all(
            isinstance(leaf, (jax.Array, np.ndarray)) for leaf in leaves)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        at = [i for i, a in enumerate(args) if traced(a)]
        named = [k for k, a in kwargs.items() if traced(a)]

        def program(by_place, by_name):
            filled = list(args)
            for i, a in zip(at, by_place):
                filled[i] = a
            return fn(*filled, **{**kwargs, **dict(zip(named, by_name))})

        return jax.jit(program)([args[i] for i in at],
                                [kwargs[k] for k in named])

    return call


@jitted
def applied(model, params, *args, **kwargs):
    """`model.apply({"params": params}, ...)` as one program."""
    return model.apply({"params": params}, *args, **kwargs)


def fresh_params(model, seed: int, move=None):
    """The parameters of `model` as an engine of this seed makes them, then
    `move`d, each through ONE program (the engine's own `init_params` runs
    an op at a time: 13 s for `tiny-gigachat`, 3 s so; equal to rounding,
    not to the bit)."""
    tree = jitted(init_params)(model, jnp.zeros((1, 8), jnp.int32),
                               jax.random.PRNGKey(seed))
    return jitted(move)(tree) if move else tree


def _frozen(value: Any):
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


def tiny_engine(model: str = "tiny", *, params=None, twin=None,
                **config) -> LLMEngine:
    """The running module's engine of this configuration, renewed: built by
    the first call that names these fields (and this `twin`: any hashable
    that tells a second engine of one configuration from the first), found
    again by every later one. `params` is read by the call that builds: a
    tree for the constructor, or a function of the seed's own tree (weights
    moved off their centres)."""
    made = EngineConfig(**{**DEFAULTS, **config, "model": model})
    key = (_frozen(dataclasses.asdict(made)), twin)
    engine = _open.get(key)
    if engine is not None and engine.has_work():
        # a case left it in the middle (it failed): the next gets its own
        _open.pop(key).close()
        engine = None
    if engine is not None:
        return renewed(engine)
    engine = _open[key] = _built(made, params)
    return engine


def new_engine(model: str = "tiny", *, params=None, **config) -> LLMEngine:
    """An engine of its own, for a test that asserts on what a first use
    builds or traces its programs under a patch: built as `tiny_engine`
    builds one and kept by nobody (its test closes or drops it)."""
    return _built(EngineConfig(**{**DEFAULTS, **config, "model": model}),
                  params)


def _built(config: EngineConfig, params) -> LLMEngine:
    own = params is None or callable(params)
    engine = LLMEngine(config, params={} if own else params)
    if own:
        compute = engine.compute
        compute.params = compute._place(
            fresh_params(compute.model, config.seed, params))
    return engine


def renewed(engine: LLMEngine) -> LLMEngine:
    """`engine` as its constructor leaves one, over the programs and
    parameters it has: what `LLMEngine.__init__` does after
    `_build_compute`, and what `StageCompute.__init__` makes an engine
    after its parameters (the pool and the carries of the decode and block
    programs). A method that a case replaced on the engine is the class's
    again. Refuses an engine with work: a request would hold pages of a
    pool that is gone."""
    if engine.has_work():
        raise RuntimeError("only an idle engine is renewed: this one has "
                           f"{engine.stats()['running']} running and "
                           f"{engine.stats()['waiting']} waiting")
    for name in [n for n in vars(engine)
                 if callable(getattr(type(engine), n, None))]:
        delattr(engine, name)
    # the finaliser of the host state that goes (its list is empty)
    atexit.unregister(engine._leave)
    engine._leave.detach()
    compute, config = engine.compute, engine.config
    compute.kv_pages = compute.fresh_pool()
    for carry in ("slot_ids", "block_ids"):
        if hasattr(compute, carry):
            old = getattr(compute, carry)
            setattr(compute, carry, jax.device_put(
                jnp.zeros(old.shape, old.dtype), old.sharding))
    engine.allocator = PageAllocator(
        config.num_pages, config.page_size,
        shard_degree=engine.sharding.tp if engine.sharding else 1)
    engine._init_host_state()
    return engine


@contextlib.contextmanager
def scarce(engine: LLMEngine, pages: int):
    """`engine` with `pages` free pages: the rest are held, as other
    requests' would be, and given back on the way out. Page pressure
    without a pool of another size, whose every program would be traced
    and compiled again."""
    allocator = engine.allocator
    held = allocator.allocate(allocator.num_free() - pages)
    try:
        yield engine
    finally:
        if engine.allocator is allocator:       # not renewed meanwhile
            allocator.release(held)


def close_all() -> None:
    """The module is over: leave its engines before their programs go."""
    while _open:
        _open.popitem()[1].close()
