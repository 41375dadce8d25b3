"""Models built and differentiated as one jitted program per call.

Flax's ``init`` and ``apply``, and ``jax.grad`` of them, called outside
``jax.jit`` run op by op, and every op at a new shape is compiled on its own:
a test that builds a tiny model spends most of its time compiling single ops.
These helpers compile each call as one program instead.

A jitted init is not bit-identical to an eager one (leaves move by a few
1e-9, a loss by an ulp; ``tests/models/test_programs.py`` pins how far), so a
comparison builds both of its sides the same way.

Positional arguments that are Python scalars or ``None`` and every keyword
argument other than ``rngs`` are static: they are closed over and pick the
program (``deterministic``, ``output_hidden_states``, ``mutable``, ...). The
rest are traced. Nothing is cached between calls: a module read under one
environment is never served a program traced under another.
"""

import jax

_STATIC = (bool, int, float, str, type(None))


def call(fn, *args, **static):
    """``fn(*args, **static)`` compiled as one program."""
    fixed = {i: a for i, a in enumerate(args) if isinstance(a, _STATIC)}

    def program(*traced):
        rest = iter(traced)
        return fn(*(fixed[i] if i in fixed else next(rest) for i in range(len(args))), **static)

    return jax.jit(program)(*(a for i, a in enumerate(args) if i not in fixed))


def init(model, key, *args, **static):
    """``model.init(key, *args, **static)`` as one program."""
    return call(model.init, key, *args, **static)


def apply(model, params, *args, rngs=None, **static):
    """``model.apply(params, *args, rngs=rngs, **static)`` as one program."""
    return call(lambda p, r, *a: model.apply(p, *a, rngs=r, **static), params, rngs, *args)


def _loss(model, static):
    return lambda p, b, r: model.apply(p, b, rngs=r, **static).loss


def loss(model, params, batch, rngs=None, **static):
    """The model output's ``loss`` on ``batch``, as one program."""
    return call(_loss(model, static), params, batch, rngs)


def grad(model, params, batch, rngs=None, **static):
    """The gradient of ``loss`` in ``params``, as one program."""
    return call(jax.grad(_loss(model, static)), params, batch, rngs)


def loss_and_grad(model, params, batch, rngs=None, **static):
    """``loss`` and its gradient in ``params``, as one program."""
    return call(jax.value_and_grad(_loss(model, static)), params, batch, rngs)
