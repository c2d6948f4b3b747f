"""int8 compression for cross-replica sync and sharded-bundle shipping.

Two consumers of the same symmetric per-tensor int8 scheme:

  * **gradients** — classic EF-SGD: quantize (grad + carried error) to int8,
    all-reduce the small payload, and carry the quantization residual into
    the next step — the time-averaged applied update is unbiased (the
    residual telescopes).

  * **deployed KAN bundles** — checkpoint shipping for sharded deployments:
    :func:`compress_deployed_kan` GATHERS a (possibly mesh-sharded) bundle's
    padded weights to host and int8-compresses each leaf;
    :func:`decompress_deployed_kan` decodes and SCATTERS the payload back
    onto a target mesh via ``deployed_kan_pspecs`` — so a bundle placed on
    one mesh can ship as a ~4x-smaller payload and land on a different mesh
    (or none) at the receiving end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "init_error_feedback",
    "compressed_grad_sync",
    "compress_deployed_kan",
    "decompress_deployed_kan",
    "_quantize",
]


def _quantize(g: jax.Array):
    """Symmetric per-tensor int8: returns (q int8, scale f32)."""
    g = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.abs(g).max(), 1e-30) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def init_error_feedback(params):
    """Zero residual tree, shaped like the gradients."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_grad_sync(grads, error_feedback, axis_name: str = "data"):
    """Quantize + psum-mean gradients inside a pmap/shard_map collective.

    Returns (synced_grads, new_error_feedback).  Call under a mapped axis
    named ``axis_name``; the int8 payload is what crosses the interconnect.
    """
    def one(g, e):
        q, s = _quantize(g.astype(jnp.float32) + e)
        deq = q.astype(jnp.float32) * s
        new_e = (g.astype(jnp.float32) + e) - deq
        synced = jax.lax.pmean(deq, axis_name)
        return synced, new_e

    pairs = jax.tree.map(one, grads, error_feedback)
    synced = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    new_ef = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return synced, new_ef


# ----------------------------------------------------------------------------
# deployed-KAN bundle shipping (gather -> compress -> scatter)
# ----------------------------------------------------------------------------


def compress_deployed_kan(dep) -> dict:
    """Gather a deployed-KAN bundle to host and int8-compress its weights.

    Works on placed (mesh-sharded) and unplaced bundles alike —
    ``device_get`` reassembles sharded leaves to their global shape.  The
    shared SH-LUT ships in raw f32 (it is tiny and the whole datapath's
    precision anchor); the padded ``wc``/``wb`` matrices — the bulk of a
    bundle — ship as (int8 codes, f32 scale).  Returns a host-side payload
    dict for :func:`decompress_deployed_kan`.
    """
    import dataclasses

    layers = []
    for lw in dep.layers:
        entry = {}
        for k, leaf in lw.items():
            a = np.asarray(jax.device_get(leaf))
            if a.dtype == np.int8:
                # already int4-packed storage ("wcp"): ship verbatim
                entry[k] = a
            elif k.startswith("lut") or k == "wscale":
                # tiny precision anchors: raw f32
                entry[k] = np.asarray(a, np.float32)
            else:
                # pure host-side codec (numpy mirror of _quantize): the
                # gather already brought the leaf to host, so no device
                # round-trip
                a = np.asarray(a, np.float32)
                s = max(float(np.abs(a).max()), 1e-30) / 127.0
                q = np.clip(np.round(a / s), -127, 127).astype(np.int8)
                entry[k] = (q, float(s))
        layers.append(entry)
    return {
        "layers": layers,
        "dims": tuple(int(d) for d in dep.dims),
        "specs": tuple(dataclasses.astuple(s) for s in dep.specs),
        "residual_raw": bool(dep.residual_raw),
    }


def decompress_deployed_kan(payload: dict, dep, mesh=None):
    """Decode a compressed bundle and scatter it onto ``mesh``.

    ``dep`` supplies the geometry/specs template (the receiving end's
    ``DeployedKAN``, e.g. freshly deployed from the same quantized params);
    its weight values are replaced by the decoded payload.  With ``mesh``
    the decoded layers are placed per ``deployed_kan_pspecs`` and the
    returned bundle records the placement, so it executes sharded without
    further ceremony; ``mesh=None`` returns a host-resident bundle.
    """
    import dataclasses

    from ..core.kan_network_deploy import place_deployed_kan

    specs = tuple(dataclasses.astuple(s) for s in dep.specs)
    if (tuple(payload["dims"]) != tuple(dep.dims)
            or bool(payload["residual_raw"]) != bool(dep.residual_raw)
            or tuple(payload["specs"]) != specs):
        raise ValueError(
            f"payload geometry {payload['dims']} (residual_raw="
            f"{payload['residual_raw']}) does not match bundle {dep.dims} "
            f"(residual_raw={dep.residual_raw}) / its quantization specs"
        )
    layers = []
    for entry in payload["layers"]:
        lw = {}
        for k, v in entry.items():
            if isinstance(v, tuple):
                q, s = v
                lw[k] = jnp.asarray(q, jnp.float32) * jnp.float32(s)
            else:
                lw[k] = jnp.asarray(v)  # raw leaf, dtype preserved
        layers.append(lw)
    out = dataclasses.replace(dep, layers=tuple(layers), placement=None)
    if mesh is not None:
        out = place_deployed_kan(out, mesh)
    return out
