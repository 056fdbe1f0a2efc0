"""The Mamba-2 state-space mixer of the hybrid families (Granite-4.0-H,
Falcon-H1): ``[z | xBC | dt] = W_in u``; ``xBC = silu(causal depthwise
conv1d(xBC) + b)``; ``dt = softplus(dt + dt_bias)``; per head ``h`` of group
``g = h // (H / G)``: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T``,
``y_t = S_t C_{g,t} + D_h x_t``; ``y = rmsnorm_per_group(y * silu(z)) * w``;
``W_out y``.  Written once, as pure functions over a mixer's parameters, and
every serve program calls them: a prefill runs the recurrence by chunks (:func:`mamba2_prefill` over
:func:`ssd_chunked`), a decode step one step of it for every slot
(:func:`mamba2_step` over ``kernels.ssm_step``, the kernel or its XLA leg), and
a decode step that CARRIES a prompt both (:func:`mamba2_ride`: the slots' rows
and the prompt's are one array through ``W_in`` and through the gate, the norm
and ``W_out``, so each weight is read once for both; between them the slots'
rows take the one step and the prompt's the chunked scan, each as it runs
alone, and a slot that holds no request keeps its state and its tail bit for bit).

``c`` is the family's config (its ``mamba_*`` fields, ``d_inner``, ``conv_dim``,
``ssm_state_shape``, ``rms_norm_eps``, ``dtype``, ``state_dtype``).  The
functions are written for ``G = mamba_n_groups`` groups of B and C, the gated
norm over each group's ``d_inner / G`` channels.  Where there is one group
(Granite) ``B`` and ``C`` carry no group axis, ``(T, N)``, and the arithmetic is
what it was before groups were written; where there are several (Falcon-H1:
two) they are ``(T, G, N)``.  The projections' operands are ``c.dtype`` with
float32 accumulation; the gate, the norm and the recurrence float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .blocks import F32, _mm, rmsnorm

__all__ = ["mamba2_prefill", "mamba2_step", "mamba2_ride", "ssd_chunked", "SCAN_PRECISION"]

# the scan's own products (C B^T, the decayed sums, the chunk states) are a few
# per cent of a prefill's operations; in float32 they leave the state exact to
# the recurrence's own rounding
SCAN_PRECISION = jax.lax.Precision.HIGHEST


def _mamba_in(c, mp, u, scale=None):
    """``[z | xBC | dt] = W_in u``, times ``scale`` (in_proj_dim,) where a
    model multiplies the projection's segments; xBC in the weights' type, as the
    convolution tail is kept (prefill and decode then convolve the same values)."""
    zxbcdt = _mm(u, mp["in_proj"], c.dtype)
    if scale is not None:
        zxbcdt = zxbcdt * scale
    z = zxbcdt[..., : c.d_inner]
    xBC = zxbcdt[..., c.d_inner: c.d_inner + c.conv_dim].astype(c.dtype)
    dt = jax.nn.softplus(zxbcdt[..., c.d_inner + c.conv_dim:] + mp["dt_bias"].astype(F32))
    return z, xBC, dt


def _mamba_split(c, conv_out):
    """``x`` (..., H, P) and ``B``, ``C``: (..., N) of one group, (..., G, N) of several."""
    act = jax.nn.silu(conv_out)
    G, GN = c.mamba_n_groups, c.mamba_n_groups * c.mamba_d_state
    x = act[..., : c.d_inner].reshape(act.shape[:-1] + (c.mamba_n_heads, c.mamba_d_head))
    B = act[..., c.d_inner: c.d_inner + GN]
    C = act[..., c.d_inner + GN:]
    if G > 1:
        B, C = (a.reshape(a.shape[:-1] + (G, c.mamba_d_state)) for a in (B, C))
    return x, B, C


def _mamba_out(c, mp, y, z):
    """Gate first, then the norm over each group's share of ``d_inner`` (all of
    it where there is one group), then ``W_out``."""
    y = y.reshape(y.shape[:-2] + (c.d_inner,)) * jax.nn.silu(z)
    if c.mamba_n_groups == 1:
        y = rmsnorm(y, mp["norm_weight"], c.rms_norm_eps)
    else:
        grouped = y.shape[:-1] + (c.mamba_n_groups, c.d_inner // c.mamba_n_groups)
        y = rmsnorm(y.reshape(grouped), mp["norm_weight"].reshape(grouped[-2:]), c.rms_norm_eps).reshape(y.shape)
    return _mm(y, mp["out_proj"], c.dtype)


def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None):
    """The recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t =
    h_t C_t`` over one sequence by chunks (Mamba-2's state-space duality):
    inside a chunk a masked, decay-weighted ``(C B^T)`` product, between
    chunks a scan over the chunk states.  ``x`` (T, H, P), ``dt`` (T, H),
    ``A`` (H,), ``B`` and ``C`` (T, N), or (T, G, N) where the heads read ``G``
    groups (head ``h`` group ``h // (H / G)``); T a multiple of ``chunk``.
    Returns ``y`` (T, H, P) and the state after the last position (H, P, N),
    float32.  A position whose ``dt`` is 0 decays nothing and adds nothing."""
    if B.ndim == 2:
        return _ssd_one_group(x, dt, A, B, C, chunk, initial_state)
    T, H, P = x.shape
    G, N = B.shape[1:]
    if H % G:
        raise ValueError(f"{H} heads do not divide into {G} groups")
    # a group is a scan of its own over its heads: the one-group arithmetic, once a group
    heads = lambda a: a.reshape(a.shape[:1] + (G, H // G) + a.shape[2:])
    h0 = jnp.zeros((H, P, N), F32) if initial_state is None else initial_state
    y, last = jax.vmap(lambda *group: _ssd_one_group(*group[:5], chunk, group[5]), in_axes=(1, 1, 0, 1, 1, 0),
                       out_axes=(1, 0))(heads(x), heads(dt), A.reshape(G, H // G), B, C, h0.reshape(G, H // G, P, N))
    return y.reshape(T, H, P), last.reshape(H, P, N)


def _ssd_one_group(x, dt, A, B, C, chunk: int, initial_state=None):
    """:func:`ssd_chunked` where every head reads the same ``B`` and ``C`` (T, N)."""
    T, H, P = x.shape
    N = B.shape[-1]
    if T % chunk:
        raise ValueError(f"{T} positions are not a whole number of chunks of {chunk}")
    n = T // chunk
    x, dt, B, C = (a.astype(F32) for a in (x, dt, B, C))
    xd = (x * dt[..., None]).reshape(n, chunk, H, P)
    Bc, Cc = B.reshape(n, chunk, N), C.reshape(n, chunk, N)
    cs = jnp.cumsum((dt * A.astype(F32)).reshape(n, chunk, H), axis=1)          # (n, q, H), <= 0
    ein = lambda spec, *ops: jnp.einsum(spec, *ops, precision=SCAN_PRECISION)
    # inside a chunk: y_q += sum_{s<=q} (C_q . B_s) exp(cs_q - cs_s) dt_s x_s
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, cs[:, :, None, :] - cs[:, None, :, :], -jnp.inf))   # (n, q, s, H)
    y = ein("cqsh,cshp->cqhp", ein("cqn,csn->cqs", Cc, Bc)[..., None] * decay, xd)
    # what each chunk adds to the state by its end, and the scan over chunks
    added = ein("cqh,cqhp,cqn->chpn", jnp.exp(cs[:, -1:, :] - cs), xd, Bc)
    h0 = jnp.zeros((H, P, N), F32) if initial_state is None else initial_state.astype(F32)

    def over_chunks(h, inp):
        add, total = inp
        return jnp.exp(total)[:, None, None] * h + add, h

    last, before = jax.lax.scan(over_chunks, h0, (added, cs[:, -1, :]))
    y = y + ein("cqn,chpn,cqh->cqhp", Cc, before, jnp.exp(cs))
    return y.reshape(T, H, P), last


def _scan_rows(c, mp, xBC, dt, length):
    """One sequence's rows between the in-projection and the gate: the
    convolution from zeros before the start, the chunked scan from a zero state
    with ``dt`` forced to 0 past ``length``.  Returns ``y`` (T, H, P), the state
    in the cache's layout (N, H P) and type, and the tail (d_conv - 1, conv_dim)
    of the last real inputs."""
    T, K = xBC.shape[0], c.mamba_d_conv
    dt = jnp.where((jnp.arange(T) < length)[:, None], dt, 0.0)
    padded = jnp.concatenate([jnp.zeros((K - 1, c.conv_dim), xBC.dtype), xBC], axis=0)
    tail = jax.lax.dynamic_slice_in_dim(padded, length, K - 1, axis=0)
    w = mp["conv_weight"].astype(F32)
    conv = mp["conv_bias"].astype(F32) + sum(w[k] * padded[k: k + T].astype(F32) for k in range(K))
    x, B, C = _mamba_split(c, conv)
    y, state = ssd_chunked(x, dt, -jnp.exp(mp["A_log"].astype(F32)), B, C, c.mamba_chunk_size)
    y = y + mp["D"].astype(F32)[:, None] * x
    state = state.transpose(2, 0, 1).reshape(c.ssm_state_shape)          # (H, P, N) -> (N, H P)
    return y, state.astype(c.state_dtype), tail


def _step_rows(c, mp, xBC, dt, ssm, tail, *, layer, interpret, active=None):
    """Every slot's one row between the in-projection and the gate: the
    convolution over the slot's tail and the new input, one step of the
    recurrence on the ``layer``-th state (``kernels.ssm_step``).  ``active``
    (S,) bool, where given, names the slots that hold a request: every other
    slot takes ``decay = 1`` and ``dt x = 0`` (selected, not multiplied: ``h' =
    1 h + B (x) 0`` is ``h`` exactly in float32), so its state stands bit for
    bit though the kernel reads and writes it.  Returns ``y`` (S, H, P), ``ssm``
    advanced, and the convolution's window (S, d_conv, conv_dim), whose last
    ``d_conv - 1`` rows are the new tail."""
    from ..kernels.ssm_step import ssm_step     # (Pallas comes with it: imported late)

    window = jnp.concatenate([tail, xBC[:, None, :].astype(tail.dtype)], axis=1)      # (S, K, conv_dim)
    conv = mp["conv_bias"].astype(F32) + jnp.sum(mp["conv_weight"].astype(F32)[None] * window.astype(F32), axis=1)
    x, B, C = _mamba_split(c, conv)
    decay = jnp.exp(dt * -jnp.exp(mp["A_log"].astype(F32)))                               # (S, H)
    if active is not None:
        decay = jnp.where(active[:, None], decay, 1.0)
    decay = jnp.repeat(decay, c.mamba_d_head, axis=1)
    dtx = dt[..., None] * x
    if active is not None:
        dtx = jnp.where(active[:, None, None], dtx, 0.0)
    ssm, y = ssm_step(ssm, decay, dtx.reshape(xBC.shape[0], c.d_inner), B, C, layer=layer, interpret=interpret)
    y = y.reshape(x.shape) + mp["D"].astype(F32)[:, None] * x
    return y, ssm, window


def mamba2_prefill(c, mp, u, length, *, in_scale=None):
    """One sequence ``u`` (T, E), T a multiple of the chunk, of which the
    first ``length`` positions are real.  In the pad ``dt`` is forced to 0, so
    the state stands where the prompt ends, and the convolution tail is taken
    from the prompt's last ``d_conv - 1`` real inputs (zeros before its
    start).  ``in_scale`` as :func:`_mamba_in` takes it.  Returns the mixer's
    output (T, E), the state in the cache's layout (N, H P) and type, and the
    tail (d_conv - 1, conv_dim)."""
    z, xBC, dt = _mamba_in(c, mp, u, in_scale)
    y, state, tail = _scan_rows(c, mp, xBC, dt, length)
    return _mamba_out(c, mp, y, z), state, tail


def mamba2_step(c, mp, u, ssm, tail, *, layer: int, interpret=None, in_scale=None):
    """The recurrence's one step for every slot: ``u`` (S, E), ``ssm`` the
    states of all state-space layers (layers, S, N, H P) of which this mixer's
    is the ``layer``-th, ``tail`` (S, d_conv - 1, conv_dim).
    ``kernels.ssm_step`` moves the state, on the leg ``interpret`` names (the
    kernel's flag, or None for the XLA leg); ``in_scale`` as :func:`_mamba_in`
    takes it.  Returns the output (S, E), ``ssm`` and the tail, advanced."""
    z, xBC, dt = _mamba_in(c, mp, u, in_scale)
    y, ssm, window = _step_rows(c, mp, xBC, dt, ssm, tail, layer=layer, interpret=interpret)
    return _mamba_out(c, mp, y, z), ssm, window[:, 1:]


def mamba2_ride(c, mp, u, ssm, tail, length, *, active, layer, interpret=None, in_scale=None):
    """A decode step's rows AND a prompt's through one mixer: ``u`` (S + T, E),
    the ``S`` slots' rows first, then a prompt padded to ``T`` positions (a
    multiple of the chunk) of which ``length`` are real.  ``W_in`` over all the
    rows at once, and the gate, the norm and ``W_out``; between them the slots'
    rows as :func:`mamba2_step` runs them (but that a slot ``active`` (S,) does
    not name keeps its state, :func:`_step_rows`, and its tail), the prompt's
    as :func:`mamba2_prefill` does, from a zero state.  ``layer`` may be a traced int32.  Returns the
    output (S + T, E), ``ssm`` and the tails (S, ...) advanced, and the
    prompt's own state (N, H P) and tail, which the caller writes over its
    slot's rows AFTER this step has touched them."""
    S = tail.shape[0]
    # (the barrier makes ``W_in``'s product ONE: its three parts are read far apart, on both sides of the state's
    # kernel and of the scan, and the v5e's compiler, rather than keep 10 MB of it, ran the product anew for each
    # reader, four times a layer, the weights read each time: 3 ms of a 128 rung's step, 7 of a 512's; PERF.md §6, PR 55)
    z, xBC, dt = jax.lax.optimization_barrier(_mamba_in(c, mp, u, in_scale))
    y_step, ssm, window = _step_rows(c, mp, xBC[:S], dt[:S], ssm, tail, layer=layer, interpret=interpret, active=active)
    y_scan, state, scan_tail = _scan_rows(c, mp, xBC[S:], dt[S:], length)
    tail = jnp.where(active[:, None, None], window[:, 1:], tail)
    return _mamba_out(c, mp, jnp.concatenate([y_step, y_scan]), z), ssm, tail, state, scan_tail
