"""Mesh-backed serving for the big ``models/model.py`` stack.

This is the bridge ROADMAP item 4 asked for: a
:class:`~repro.runtime.serving.GenerationSession` /
:class:`~repro.runtime.serving.ContinuousGenerationSession` whose
parameters live SHARDED across a device mesh (``launch/mesh.py`` host
mesh in tests, a TPU pod in production), so a
:class:`~repro.runtime.engine.Tier` of the ``CollaborativeEngine`` can
be a multi-device sharded LM server instead of a single-device model.

The sessions themselves need no changes: ``jax.jit`` picks up the
committed :class:`~jax.sharding.NamedSharding` of the parameters, GSPMD
partitions the prefill / compiled-scan decode executables, and the
decode state inherits propagated shardings.  What this module owns is
the *placement*: choosing a layout (``tp`` tensor-parallel vs ``ddp``
pure data-parallel, per ``sharding/policy.py``) and ``device_put``-ing
the parameter pytree under the policy's :func:`param_specs`.

Decode output is BIT-FOR-BIT equal to the unsharded single-device run
for every smoke architecture — pinned under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` in
tests/test_bigmodel_serving.py.
"""

from __future__ import annotations

from typing import Tuple

import jax

from repro.models.model import LM
from repro.runtime.serving import (
    ContinuousGenerationSession,
    GenerationSession,
)
from repro.sharding.policy import (
    ShardingPolicy,
    make_policy,
    param_specs,
    to_shardings,
)


def infer_layout(cfg, mesh) -> str:
    """Pick the policy layout for this architecture on this mesh.

    ``tp`` when the attention head counts divide the ``model`` axis (the
    TP collectives then split real work); ``ddp`` otherwise — right for
    head counts that don't divide the axis (rwkv6's 40 heads, whisper's
    20 on an 8-way axis) and for models whose mixers carry no head axis
    worth splitting (see sharding/policy.py docstring).
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = int(axes.get("model", 1))
    if tp <= 1:
        return "ddp"
    heads_ok = (cfg.num_heads % tp == 0 and cfg.num_kv_heads % tp == 0)
    has_heads = any(g.mixer in ("attn", "shared_attn", "mla")
                    for g in cfg.layer_plan)
    return "tp" if (has_heads and heads_ok) else "ddp"


def _policy(model: LM, mesh, batch_size: int, layout: str,
            fsdp: bool) -> ShardingPolicy:
    if layout == "auto":
        layout = infer_layout(model.cfg, mesh)
    return make_policy(mesh, batch_size=batch_size, layout=layout, fsdp=fsdp)


def init_sharded(model: LM, key, mesh, *, batch_size: int = 8,
                 layout: str = "auto", fsdp: bool = True
                 ) -> Tuple[object, ShardingPolicy]:
    """Initialise ``model``'s parameters directly into their shardings.

    ``model.init`` is jitted with the policy's shardings as
    ``out_shardings``, so each device draws only its own shard: the whole
    model never sits on one device (a published-width LM may not fit
    there).  Same values as ``model.init(key)``.  Returns ``(params,
    policy)`` like :func:`shard_lm`.
    """
    pol = _policy(model, mesh, batch_size, layout, fsdp)
    shardings = to_shardings(mesh, param_specs(pol, model.params_spec()))
    return jax.jit(model.init, out_shardings=shardings)(key), pol


def shard_lm(model: LM, params, mesh, *, batch_size: int = 8,
             layout: str = "auto", fsdp: bool = True
             ) -> Tuple[object, ShardingPolicy]:
    """Place existing ``params`` on ``mesh`` under the sharding policy.

    Returns ``(sharded_params, policy)``; ``layout="auto"`` delegates to
    :func:`infer_layout`.  The returned params carry committed
    NamedShardings, so any jit consuming them (the session entry points)
    compiles a partitioned executable without explicit in_shardings.
    Params that already carry these shardings are not moved.
    """
    pol = _policy(model, mesh, batch_size, layout, fsdp)
    shardings = to_shardings(
        mesh, param_specs(pol, jax.eval_shape(lambda: params)))
    return jax.device_put(params, shardings), pol


def make_sharded_session(model: LM, params, mesh, *,
                         continuous: bool = False,
                         batch_size: int = 8,
                         layout: str = "auto",
                         fsdp: bool = True,
                         max_len: int = 64,
                         max_slots: int = 8,
                         bucket_shapes: bool = True,
                         host_loop: bool = False):
    """Build a generation session whose params are sharded over ``mesh``.

    ``continuous=False`` returns a :class:`GenerationSession` (compiled
    scan decode), ``continuous=True`` a
    :class:`ContinuousGenerationSession` (slot-table in-flight batching;
    decoder-only plans).  Everything downstream — ``build_executor``,
    ``Tier``, ``CollaborativeEngine.serve_continuous`` — composes
    unchanged, which is the point: a sharded pod tier is just a tier.
    """
    params_s, pol = shard_lm(model, params, mesh, batch_size=batch_size,
                             layout=layout, fsdp=fsdp)
    if continuous:
        sess = ContinuousGenerationSession(
            model, params_s, max_slots=max_slots, max_len=max_len,
            bucket_shapes=bucket_shapes)
    else:
        sess = GenerationSession(model, params_s, max_len=max_len,
                                 host_loop=host_loop,
                                 bucket_shapes=bucket_shapes)
    sess.policy = pol            # introspection: which layout was chosen
    sess.layout = "tp" if pol.model_axes else "ddp"
    sess.mesh = mesh
    return sess
