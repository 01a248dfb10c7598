"""Global kernel dispatch policy.

``set_policy("pallas")`` flips every hot spot (attention, SSD scan,
RG-LRU scan) in the model layers onto the Pallas TPU kernels;
``"ref"`` forces the pure-XLA path (the default on CPU, and the path
the multi-pod dry-run lowers — Mosaic kernels target real TPUs).

The graph-IR runtime consumes the same policy through
:func:`select_attention_impl_per_class`: when ``runtime.program`` lowers
an ``attention`` op it asks this module — with the device-LOCAL shard
shapes and the platform of the mesh the program is placed on — whether
the Pallas flash kernel applies (``kernels.flash_attention``) or the
pure-XLA reference must run (``kernels.ref.flash_attention_ref``).
Under ``"auto"`` that platform, not the process default backend,
decides.  The decision is memoized per distinct (q, kv) shard-shape
pair and platform, so every device of a specialization
class (``core.lowered_ir``) shares ONE decision and ONE emitted branch;
it participates in the class partition (same shapes, different impl ⇒
different classes — can't happen under one policy, but the seam is
explicit).  The decision is static per compiled program and is tallied
per emitted class in ``LoweringStats``.
"""

from __future__ import annotations

VALID_POLICIES = ("auto", "pallas", "ref")

_POLICY = "auto"


def set_policy(policy: str) -> None:
    if policy not in VALID_POLICIES:
        raise ValueError(
            f"unknown kernel policy {policy!r}; valid policies: "
            f"{', '.join(VALID_POLICIES)}")
    global _POLICY
    _POLICY = policy
    _impl_cache.clear()


def get_policy() -> str:
    return _POLICY


def use_pallas(platform: str | None = None) -> bool:
    """Whether the kernels apply on ``platform`` (the platform of the
    devices a program is placed on; the process default backend when
    not given)."""
    if _POLICY != "auto":
        return _POLICY == "pallas"
    if platform is None:
        import jax
        platform = jax.default_backend()
    return platform == "tpu"


def attention_eligible(q_shape, kv_shape, *, block_q: int = 128,
                       block_k: int = 128) -> bool:
    """Whether the Pallas flash-attention kernel can take these
    device-local shards: ``q (B, H, Sq, D)``, ``k/v (B, K, Sk, D)``.
    Mirrors the kernel's own constraints (GQA head ratio, sequence
    lengths tiled by the block sizes, lane-aligned head dim)."""
    if len(q_shape) != 4 or len(kv_shape) != 4:
        return False
    _, h, sq, d = q_shape
    _, kh, sk, kd = kv_shape
    bq, bk = min(block_q, sq), min(block_k, sk)
    return (kh >= 1 and h % kh == 0 and d == kd and d % 8 == 0
            and sq % bq == 0 and sk % bk == 0)


def select_attention_impl(q_shape, kv_shape,
                          platform: str | None = None) -> str:
    """``"pallas"`` or ``"ref"`` for one device-local attention dispatch
    (the graph-IR lowering seam; see ``runtime.program``)."""
    if use_pallas(platform) and attention_eligible(q_shape, kv_shape):
        return "pallas"
    return "ref"


#: (q_shape, kv_shape, platform) -> impl; cleared on set_policy so a
#: policy flip re-decides every class
_impl_cache: dict[tuple, str] = {}


def select_attention_impl_per_class(q_shape, kv_shape,
                                    platform: str) -> str:
    """Per-class dispatch: memoized :func:`select_attention_impl` over
    distinct device-local (q, kv) shard-shape pairs on the platform of
    the program's mesh, so all devices of a specialization class
    resolve to the same kernel with one decision."""
    key = (tuple(q_shape), tuple(kv_shape), platform)
    impl = _impl_cache.get(key)
    if impl is None:
        impl = _impl_cache[key] = select_attention_impl(*key)
    return impl
