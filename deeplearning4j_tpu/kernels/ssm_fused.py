"""The elementwise chain of a Mamba-2 block as two fused Pallas operations.

Between ``in_proj`` and the scan, and between the scan and ``out_proj``, a
Mamba-2 block (`models.hybrid_lm._mamba`) does only elementwise work, one
depthwise conv over four steps and a norm over groups of channels. Left to
XLA that chain writes float32 ``[T, conv_dim]`` and ``[T, d_inner]``
tensors, split slices and layout copies to HBM, and autodiff's backward
saves more of them. Here each operand is read once in its stored dtype,
every product, sum and statistic is float32 in registers, and each result
is written once in the stored dtype; the backwards are hand-written and
keep nothing but the operations' inputs.

**Time is the minor axis.** Every operand is ``[B, channels, T]``: the
layout XLA itself gives this chain and the scan's operands when it is left
to choose (the projection writes it at no cost, and the scan's einsums
read it without a copy). A caller swaps the axes of what it hands over
and takes back; under ``jit`` those swaps are layout changes, not copies.

`mamba_chain` is the one entry: the caller hands it ``zxbcdt = [z | x B C
| dt]`` along the channels (the projection's output), the parameters and
its scan. Inside it are two operations with backwards of their own
(``jax.custom_vjp``) around the caller's scan. ``conv_silu``, from
``zxbcdt``, the conv's weight ``[K, conv_dim]`` and bias::

    xBC_t = silu(sum_k w[k] * xBC_{t-(K-1)+k} + b)        (zeros before t=0)

as three results ``x``, ``B``, ``C``, beside the raw ``dt`` channels and
``zxbcdt`` again as the handle ``zg`` that ``gate_norm`` reads ``z`` from.
``gate_norm``::

    out = GroupRMSNorm((y + D * x) * silu(z); weight, eps)

over ``groups`` equal parts of the channels, ``D`` per head. ``x`` has two
readers, the scan and the gate: ``conv_silu`` returns it under two names,
so their cotangents come back apart and are added in its backward kernel.

**One cotangent buffer.** ``zxbcdt`` feeds three consumers (the conv, the
gate, ``dt``); three cotangents of ``[B, F, T]`` summed by autodiff would
cost more bytes than the chain itself. So ``gate_norm``'s backward writes
``dz`` into the ``z`` channels of a fresh ``[B, F, T]`` buffer and returns
it as the cotangent of ``zg`` (**only those channels are defined**), and
``conv_silu``'s backward, the one reader of that cotangent, writes
``d xBC`` in place beside them (``input_output_aliases``) and ``d dt``
into the tail: ``in_proj``'s backward gets one buffer, each element
written once. That protocol is why the two are private: apart, or with a
second reader of ``zg``, their gradients are not defined; `mamba_chain`
is where they meet.

**Tiles.** A kernel works on ``[channels, steps]`` tiles of a window of
channels, taken straight from ``zxbcdt`` by the block index maps: grid
(channel chunks, batch, time tiles), time innermost. The conv reads the
``K - 1`` steps before a tile from one more 128-step block (zeroed before
the first tile). Its backward walks the time tiles from the last to the
first, recomputes the pre-activation, and carries the first steps of the
following tile's ``d pre`` in VMEM; the per-channel sums (``d w``,
``d b``, ``d D``, ``d weight``) accumulate in float32 in the output block
that stays resident over the time tiles. The norm takes one group of
channels a chunk, its statistics inside the tile: nothing is reshaped in
HBM.

Shapes that do not tile (a group or a state width that is no multiple of
16, a ``T`` that is no multiple of the time tile) are zero-padded into the
tiled layout first and run through the same kernels; ``zg`` is then that
padded copy. On the CPU the kernels run interpreted.

**Packed rows.** With ``segment_ids`` (``[B, T]`` int32, non-decreasing
along a row) the conv's tap ``x_{t-j}`` counts only where step ``t - j``
is of step ``t``'s document. One more operand says so, ``[B, 1, T]``: the
steps since the document's first, clipped to ``K - 1``; tap ``j`` is live
at ``t`` iff it is ``>= j``. The forward kernel weighs its shifted tiles
by that, the backward the same on the output position ``t + j`` of each
term of ``d x_t`` (it reads the operand's first steps of the following
tile beside its own). The gate and its norm work step by step and need
nothing. Without ``segment_ids`` no such operand exists and the kernels
traced are the ones above.

``dl4j_ssm_fused_calls_total{op,kind}`` counts the passes at trace time,
``dl4j_boundary_kernel_passes_total{kernel,kind}`` those of them that
take document boundaries.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 2048         # steps of a tile
_CHUNK = 128         # most channels of a conv tile
_HALO = 128          # steps of the block read before a tile
_ROWS = 32           # channels a pass of the conv's loop inside a tile
_STEPS = 128         # steps a pass of the gate's loop inside a tile
_SUB = 16            # channels a window starts and ends on (a bf16 tile)
_GATE_TILE = 512 * _TILE   # most elements of a gate tile: a group x steps
_VMEM_LIMIT = 64 * 1024 * 1024
F32 = jnp.float32


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _count(op: str, kind: str) -> None:
    try:
        from ..common.environment import environment
        environment().metrics().counter(
            "dl4j_ssm_fused_calls_total",
            "Passes of the fused Mamba-2 operations (conv + SiLU, skip + "
            "gate + grouped RMSNorm), forward and backward, counted at "
            "trace time",
            labels=("op", "kind")).labels(op=op, kind=kind).inc()
    except Exception:
        pass  # observability must never break a trace


def _boundary_pass(kernel: str, kind: str) -> None:
    from . import boundary_pass
    boundary_pass(kernel, kind)


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


# -- the tiled layout ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Layout:
    """Where the parts of ``zxbcdt`` lie in the tiled buffer: ``z`` and
    ``x`` as ``groups`` groups of ``gw`` channels each padded to ``gwp``,
    ``B`` and ``C`` of ``n`` channels padded to ``n_p``, ``dt`` of ``h``
    channels at the tail; ``t`` steps padded to ``t_p``, ``tile`` a tile."""
    t: int
    t_p: int
    tile: int
    groups: int
    gw: int
    gwp: int
    n: int
    n_p: int
    h: int

    @property
    def wz(self) -> int:
        return self.groups * self.gwp

    @property
    def tiled(self) -> bool:
        """The operands are in the tiled layout as they come."""
        return (self.gw, self.n, self.t) == (self.gwp, self.n_p, self.t_p)

    @property
    def gate_tile(self) -> int:
        """Steps of a gate tile, which holds a whole group of channels: the
        conv's tile, halved while a wider group than 512 channels would
        make it larger than 512 channels of the conv's."""
        tile = self.tile
        while self.gwp * tile > _GATE_TILE and tile % (2 * _HALO) == 0:
            tile //= 2
        return tile

    @property
    def chunk(self) -> int:
        """Channels of a conv tile: divides every window's offset."""
        return math.gcd(self.wz, self.n_p, _CHUNK)

    @property
    def windows(self):
        """(first channel in the tiled ``zxbcdt``, first channel in the
        tiled conv weight, channels) of ``x``, ``B``, ``C``."""
        wz, n = self.wz, self.n_p
        return [(wz, 0, wz), (2 * wz, wz, n), (2 * wz + n, wz + n, n)]


def _layout(t, d_inner, n, h, groups) -> _Layout:
    gw = d_inner // groups
    tile = min(_TILE, _up(t, _HALO))
    return _Layout(t=t, t_p=_up(t, tile), tile=tile, groups=groups, gw=gw,
                   gwp=_up(gw, _SUB), n=n, n_p=_up(n, _SUB), h=h)


def _pad_groups(a, groups: int, wp: int):
    """Axis 1 as ``groups`` groups, each zero-padded to ``wp``."""
    w = a.shape[1] // groups
    if w == wp:
        return a
    a = a.reshape((a.shape[0], groups, w) + a.shape[2:])
    a = jnp.pad(a, [(0, 0), (0, 0), (0, wp - w)] + [(0, 0)] * (a.ndim - 3))
    return a.reshape((a.shape[0], groups * wp) + a.shape[3:])


def _cut_groups(a, groups: int, w: int):
    wp = a.shape[1] // groups
    if w == wp:
        return a
    a = a.reshape((a.shape[0], groups, wp) + a.shape[2:])[:, :, :w]
    return a.reshape((a.shape[0], groups * w) + a.shape[3:])


def _pad_steps(a, lay: _Layout):
    if lay.t == lay.t_p:
        return a
    return jnp.pad(a, [(0, 0), (0, 0), (0, lay.t_p - lay.t)])


def _tile_x(a, lay: _Layout):
    """A ``[B, d_inner, T]`` operand in the tiled layout."""
    return _pad_steps(_pad_groups(a, lay.groups, lay.gwp), lay)


def _untile_x(a, lay: _Layout):
    return a if lay.tiled else _cut_groups(a[:, :, :lay.t], lay.groups,
                                           lay.gw)


def _tile_bc(a, lay: _Layout):
    """A ``[B, n, T]`` operand in the tiled layout."""
    return _pad_steps(_pad_groups(a, 1, lay.n_p), lay)


def _untile_bc(a, lay: _Layout):
    return a if lay.tiled else a[:, :lay.n, :lay.t]


def _split(a, wz: int, n: int):
    """The five parts of a ``zxbcdt``-like channel axis."""
    return jnp.split(a, [wz, 2 * wz, 2 * wz + n, 2 * wz + 2 * n], axis=1)


def _tile_zxbcdt(zxbcdt, lay: _Layout):
    if lay.tiled:
        return zxbcdt
    z, x, b, c, dt = _split(zxbcdt, lay.groups * lay.gw, lay.n)
    return _pad_steps(jnp.concatenate(
        [_pad_groups(z, lay.groups, lay.gwp),
         _pad_groups(x, lay.groups, lay.gwp),
         _pad_groups(b, 1, lay.n_p), _pad_groups(c, 1, lay.n_p), dt], 1), lay)


def _untile_zxbcdt(a, lay: _Layout):
    if lay.tiled:
        return a
    z, x, b, c, dt = _split(a[:, :, :lay.t], lay.wz, lay.n_p)
    return jnp.concatenate(
        [_cut_groups(z, lay.groups, lay.gw),
         _cut_groups(x, lay.groups, lay.gw),
         b[:, :lay.n], c[:, :lay.n], dt], 1)


def _tile_conv(w, lay: _Layout):
    """The conv's weight ``[K, conv_dim]`` (or its bias as ``[1, ...]``)
    as float32 ``[x | B | C channels of the tiled layout, K]``."""
    wx = lay.groups * lay.gw
    w = w.astype(F32).T[None]
    return jnp.concatenate(
        [_pad_groups(w[:, :wx], lay.groups, lay.gwp),
         _pad_groups(w[:, wx:wx + lay.n], 1, lay.n_p),
         _pad_groups(w[:, wx + lay.n:], 1, lay.n_p)], 1)[0]


def _untile_conv(w, lay: _Layout):
    """`_tile_conv`'s inverse: ``[channels, K]`` -> ``[K, conv_dim]``."""
    w = w[None]
    return jnp.concatenate(
        [_cut_groups(w[:, :lay.wz], lay.groups, lay.gw),
         w[:, lay.wz:lay.wz + lay.n],
         w[:, lay.wz + lay.n_p:lay.wz + lay.n_p + lay.n]], 1)[0].T


def _shared_pass(*static):
    """The decorator of a pass builder: an inner ``jit``, only ever traced
    inside a caller's jitted step, so that every block's call site of a
    pass shares one traced and lowered function."""
    return functools.partial(jax.jit, static_argnames=static)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3,
        vmem_limit_bytes=_VMEM_LIMIT)


def _sigmoid(v):
    return 1.0 / (1.0 + jnp.exp(-v))


# -- conv + silu ----------------------------------------------------------------

def _each(n: int, size: int, body):
    """``body(at)`` for each aligned slice ``at`` of ``size`` out of ``n``,
    as a loop inside the kernel: its code is one slice's, however large
    the tile."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * size, size), size))
        return carry
    jax.lax.fori_loop(0, n // size, step, 0)


def _taps(x, halo, has_before, k: int):
    """``[x_t, x_{t-1}, ..., x_{t-(k-1)}]`` as float32 tiles: the tile
    after the steps before it, shifted a step a tap."""
    halo = jnp.where(has_before, halo.astype(F32), 0.0)
    ext = jnp.concatenate([halo, x.astype(F32)], axis=1)
    return [(pltpu.roll(ext, j, 1) if j else ext)[:, _HALO:]
            for j in range(k)]


def _live(since, k: int):
    """``[1, steps]`` float32 weights of taps 1 .. k-1 from the steps
    since the document's start: 1 where the tap's step is of the same
    document, else 0."""
    since = since.astype(F32)
    return [jnp.where(since >= j, 1.0, 0.0) for j in range(1, k)]


def _same_document(taps, live):
    """The taps with those that reach before the document's start
    zeroed (``live`` None: one document a row)."""
    if live is None:
        return taps
    return taps[:1] + [t * m for t, m in zip(taps[1:], live)]


def _pre(taps, w_ref, b_ref, at):
    k = w_ref.shape[1]
    pre = b_ref[at] + taps[0] * w_ref[at, k - 1:k]
    for j in range(1, k):
        pre = pre + taps[j] * w_ref[at, k - 1 - j:k - j]
    return pre


def _conv_kernel(x_ref, halo_ref, w_ref, b_ref, *refs):
    # refs: [the steps since the document's start,] the result
    o_ref = refs[-1]
    has_before = pl.program_id(2) > 0
    k, ch = w_ref.shape[1], x_ref.shape[1]
    live = _live(refs[0][0], k) if len(refs) > 1 else None

    def rows(at):
        pre = _pre(_same_document(
            _taps(x_ref[0, at], halo_ref[0, at], has_before, k), live),
            w_ref, b_ref, at)
        o_ref[0, at] = (pre * _sigmoid(pre)).astype(o_ref.dtype)

    _each(ch, math.gcd(ch, _ROWS), rows)


def _conv_bwd_kernel(x_ref, halo_ref, *refs, n_t, n_g, packed):
    g_refs, (w_ref, b_ref, _), refs = refs[:n_g], refs[n_g:n_g + 3], \
        refs[n_g + 3:]
    since_refs, (dx_ref, dw_ref, db_ref, carry) = refs[:-4], refs[-4:]
    b, i = pl.program_id(1), pl.program_id(2)     # i counts from the end
    k, (ch, tile) = w_ref.shape[1], x_ref.shape[1:]
    live = after = None
    if packed:
        # the tile's own steps, then the first of the tile after it
        since, since_after = (r[0].astype(F32) for r in since_refs)
        live = _live(since, k)
        ext_since = jnp.broadcast_to(
            jnp.concatenate([since, since_after], axis=1), (8, tile + _HALO))
        # whether d pre_{t+j} counts x_t: step t + j's tap j is live
        after = [jnp.where(pltpu.roll(ext_since, tile + _HALO - j, 1)
                           [:1, :tile] >= j, 1.0, 0.0) for j in range(1, k)]

    @pl.when(i == 0)
    def _last_tile():
        carry[...] = jnp.zeros_like(carry)

    @pl.when(jnp.logical_and(b == 0, i == 0))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    def rows(at):
        taps = _same_document(
            _taps(x_ref[0, at], halo_ref[0, at], i < n_t - 1, k), live)
        pre = _pre(taps, w_ref, b_ref, at)
        s = _sigmoid(pre)
        g = sum(g_ref[0, at].astype(F32) for g_ref in g_refs)
        dpre = g * (s * (1.0 + pre * (1.0 - s)))
        # d x_t = sum_j w[K-1-j] d pre_{t+j}: the tile before the first
        # steps of the tile after it, shifted back a step a tap
        ext = jnp.concatenate([dpre, carry[at]], axis=1)
        dx = dpre * w_ref[at, k - 1:k]
        for j in range(1, k):
            term = pltpu.roll(ext, tile + _HALO - j, 1)[:, :tile]
            if packed:
                term = term * after[j - 1]
            dx = dx + term * w_ref[at, k - 1 - j:k - j]
        dx_ref[0, at] = dx.astype(dx_ref.dtype)
        carry[at] = dpre[:, :_HALO]
        for j in range(k):
            dw_ref[at, k - 1 - j:k - j] += jnp.sum(dpre * taps[j], axis=1,
                                                   keepdims=True)
        db_ref[at] += jnp.sum(dpre, axis=1, keepdims=True)

    _each(ch, math.gcd(ch, _ROWS), rows)


def _window_specs(lay: _Layout, k: int, src: int, w_src: int, reverse: bool):
    """(tile spec at a first channel, [source tile, the steps before it,
    weight, bias] specs) of one window of the conv; ``reverse`` walks the
    time tiles from the last to the first."""
    tile, ch = lay.tile, lay.chunk
    n_t, per = lay.t_p // tile, tile // _HALO
    step = (lambda i: n_t - 1 - i) if reverse else (lambda i: i)

    def at(first):
        return pl.BlockSpec((1, ch, tile),
                            lambda c, b, i: (b, first // ch + c, step(i)))

    return at, [
        at(src),
        pl.BlockSpec((1, ch, _HALO), lambda c, b, i: (
            b, src // ch + c, jnp.maximum(step(i) * per - 1, 0))),
        pl.BlockSpec((ch, k), lambda c, b, i: (w_src // ch + c, 0)),
        pl.BlockSpec((ch, 1), lambda c, b, i: (w_src // ch + c, 0)),
    ]


def _since_specs(lay: _Layout, reverse: bool):
    """The ``[B, 1, T]`` operand of a packed row: [a tile's own steps,
    the first steps of the tile after it (the backward's)]."""
    tile = lay.tile
    n_t, per = lay.t_p // tile, tile // _HALO
    step = (lambda i: n_t - 1 - i) if reverse else (lambda i: i)
    return [
        pl.BlockSpec((1, 1, tile), lambda c, b, i: (b, 0, step(i))),
        pl.BlockSpec((1, 1, _HALO), lambda c, b, i: (
            b, 0, jnp.minimum((step(i) + 1) * per, n_t * per - 1))),
    ]


def _conv_window(zp, w_p, b_p, since, lay: _Layout, src, w_src, width):
    """conv + silu of the channels [src, src + width) of ``zp``."""
    bsz, tile, ch = zp.shape[0], lay.tile, lay.chunk
    _, in_specs = _window_specs(lay, w_p.shape[1], src, w_src, False)
    packed = () if since is None else (since,)
    if packed:
        in_specs = in_specs + _since_specs(lay, False)[:1]
    return pl.pallas_call(
        _conv_kernel,
        grid=(width // ch, bsz, lay.t_p // tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, ch, tile), lambda c, b, i: (b, c, i)),
        out_shape=jax.ShapeDtypeStruct((bsz, width, lay.t_p), zp.dtype),
        compiler_params=_params(), interpret=_interpret(),
    )(zp, zp, w_p, b_p, *packed)


def _conv_window_bwd(zp, gs, w_p, b_p, dbuf, since, lay: _Layout, src,
                     w_src, width):
    """(``dbuf`` with the window's ``d xBC`` written into its channels in
    place, ``d w`` [width, K], ``d b`` [width, 1]); ``gs`` the window's
    cotangents, added up in the kernel."""
    bsz, tile, ch, k = zp.shape[0], lay.tile, lay.chunk, w_p.shape[1]
    n_t = lay.t_p // tile
    at, in_specs = _window_specs(lay, k, src, w_src, True)
    acc = lambda lanes: pl.BlockSpec((ch, lanes), lambda c, b, i: (c, 0))
    packed = () if since is None else (since, since)
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, n_t=n_t, n_g=len(gs),
                          packed=bool(packed)),
        grid=(width // ch, bsz, n_t),
        in_specs=in_specs[:2] + [at(0)] * len(gs) + in_specs[2:]
        + [pl.BlockSpec(memory_space=pl.ANY)]
        + (_since_specs(lay, True) if packed else []),
        out_specs=[at(src), acc(k), acc(1)],
        out_shape=[jax.ShapeDtypeStruct(dbuf.shape, dbuf.dtype),
                   jax.ShapeDtypeStruct((width, k), F32),
                   jax.ShapeDtypeStruct((width, 1), F32)],
        scratch_shapes=[pltpu.VMEM((ch, _HALO), F32)],
        input_output_aliases={4 + len(gs): 0},
        compiler_params=_params(), interpret=_interpret(),
    )(zp, zp, *gs, w_p, b_p, dbuf, *packed)


@_shared_pass("lay")
def _conv_fwd(zp, conv_w, conv_b, lay: _Layout, since=None):
    w_p, b_p = _tile_conv(conv_w, lay), _tile_conv(conv_b[None], lay)
    return tuple(_conv_window(zp, w_p, b_p, since, lay, *win)
                 for win in lay.windows)


@_shared_pass("lay")
def _conv_bwd(zp, gs, gdt, dbuf, conv_w, conv_b, lay: _Layout, since=None):
    w_p, b_p = _tile_conv(conv_w, lay), _tile_conv(conv_b[None], lay)
    dws, dbs = [], []
    for g, win in zip(gs, lay.windows):
        dbuf, dw, db = _conv_window_bwd(zp, g, w_p, b_p, dbuf, since, lay,
                                        *win)
        dws.append(dw)
        dbs.append(db)
    if lay.h:
        dbuf = dbuf.at[:, dbuf.shape[1] - lay.h:].set(
            _pad_steps(gdt, lay).astype(dbuf.dtype))
    return (dbuf, _untile_conv(jnp.concatenate(dws), lay),
            _untile_conv(jnp.concatenate(dbs), lay)[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv_silu(zxbcdt, conv_w, conv_b, since, d_inner: int, groups: int):
    """``(x [B,d_inner,T], x again, B [B,n,T], C [B,n,T], dt [B,h,T],
    zg)`` from ``zxbcdt`` [B, 2 d_inner + 2 n + h, T]: the conv and silu
    over the ``x | B | C`` channels; ``dt`` the last ``h`` channels as
    they are; ``zg`` what `_gate_norm` reads ``z`` from (``zxbcdt`` itself
    where it tiles). ``x`` comes twice, one array under two names, for its
    two readers (the scan and `_gate_norm`): their cotangents then reach
    the backward kernel apart and are added in its registers, not by a
    pass over HBM. ``groups`` is the gate norm's (it decides the padding
    of a shape that does not tile). ``since``: None, or for packed rows
    the int32 ``[B, 1, T]`` steps since each step's document began,
    clipped to ``K - 1`` (`_steps_since_start`).

    Half of a pair, for `mamba_chain` alone: its backward takes ``zg``'s
    cotangent as the buffer it completes in place, so ``zg`` has to have
    no reader, or `_gate_norm` as its one reader."""
    return _conv_silu_f(zxbcdt, conv_w, conv_b, since, d_inner, groups)[0]


def _conv_layout(conv_w, d_inner, h, t, groups) -> _Layout:
    if conv_w.shape[0] - 1 > _HALO:
        raise ValueError("the conv reads more steps than a tile's halo holds")
    return _layout(t, d_inner, (conv_w.shape[1] - d_inner) // 2, h, groups)


def _conv_silu_f(zxbcdt, conv_w, conv_b, since, d_inner, groups):
    _count("conv_silu", "fwd")
    f, t = zxbcdt.shape[1:]
    lay = _conv_layout(conv_w, d_inner, f - d_inner - conv_w.shape[1], t,
                       groups)
    zp = _tile_zxbcdt(zxbcdt, lay)
    if since is not None:
        _boundary_pass("conv_silu", "fwd")
        since = _pad_steps(since, lay)
    x, bm, cm = _conv_fwd(zp, conv_w, conv_b, lay, since)
    x = _untile_x(x, lay)
    return ((x, x, _untile_bc(bm, lay), _untile_bc(cm, lay),
             zxbcdt[:, f - lay.h:], zp), (zp, conv_w, conv_b, since))


def _conv_silu_b(d_inner, groups, res, cts):
    _count("conv_silu", "bwd")
    zp, conv_w, conv_b, since = res
    *gxs, gb, gc, gdt, dbuf = cts
    lay = _conv_layout(conv_w, d_inner, gdt.shape[1], gb.shape[2], groups)
    gs = (tuple(_tile_x(g, lay) for g in gxs), (_tile_bc(gb, lay),),
          (_tile_bc(gc, lay),))
    if since is not None:
        _boundary_pass("conv_silu", "bwd")
    dbuf, dw, db = _conv_bwd(zp, gs, gdt, dbuf, conv_w, conv_b, lay, since)
    return (_untile_zxbcdt(dbuf, lay), dw.astype(conv_w.dtype),
            db.astype(conv_b.dtype), None)


_conv_silu.defvjp(_conv_silu_f, _conv_silu_b)


# -- skip + gate + grouped RMSNorm ----------------------------------------------

def _gated(y, x, z, d):
    """(y + D x, z, sigmoid(z)) of a tile, float32."""
    z = z.astype(F32)
    return y.astype(F32) + d * x.astype(F32), z, _sigmoid(z)


def _rstd(yg, eps, gw):
    return jax.lax.rsqrt(jnp.sum(yg * yg, axis=0, keepdims=True) / gw + eps)


def _gate_kernel(y_ref, x_ref, z_ref, d_ref, w_ref, o_ref, *, eps, gw):
    tile = y_ref.shape[2]

    def steps(at):
        a, z, s = _gated(y_ref[0, :, at], x_ref[0, :, at], z_ref[0, :, at],
                         d_ref[...])
        yg = a * (z * s)
        o_ref[0, :, at] = (yg * _rstd(yg, eps, gw) * w_ref[...]).astype(
            o_ref.dtype)

    _each(tile, math.gcd(tile, _STEPS), steps)


def _gate_bwd_kernel(g_ref, y_ref, x_ref, z_ref, d_ref, w_ref, dy_ref,
                     dx_ref, dz_ref, dd_ref, dw_ref, *, eps, gw):
    tile = y_ref.shape[2]

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, pl.program_id(2) == 0))
    def _init():
        dd_ref[...] = jnp.zeros_like(dd_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def steps(at):
        x = x_ref[0, :, at].astype(F32)
        a, z, s = _gated(y_ref[0, :, at], x, z_ref[0, :, at], d_ref[...])
        silu = z * s
        yg = a * silu
        r = _rstd(yg, eps, gw)
        n = yg * r
        g = g_ref[0, :, at].astype(F32)
        dw_ref[...] += jnp.sum(g * n, axis=1, keepdims=True)
        dn = g * w_ref[...]
        dyg = r * (dn - n * (jnp.sum(dn * n, axis=0, keepdims=True) / gw))
        da = dyg * silu
        dy_ref[0, :, at] = da.astype(dy_ref.dtype)
        dx_ref[0, :, at] = (da * d_ref[...]).astype(dx_ref.dtype)
        dd_ref[...] += jnp.sum(da * x, axis=1, keepdims=True)
        dz_ref[0, :, at] = (dyg * a * (s * (1.0 + z * (1.0 - s)))).astype(
            dz_ref.dtype)

    _each(tile, math.gcd(tile, _STEPS), steps)


def _gate_call(kernel, lay: _Layout, eps, bsz, n_tiles, out_shape):
    """One group of channels a chunk: ``n_tiles`` tile operands, then the
    two per-channel columns ``D`` and ``weight``; a result is a tile or
    such a column by its rank."""
    tile = pl.BlockSpec((1, lay.gwp, lay.gate_tile),
                        lambda c, b, i: (b, c, i))
    col = pl.BlockSpec((lay.gwp, 1), lambda c, b, i: (c, 0))
    return pl.pallas_call(
        functools.partial(kernel, eps=eps, gw=lay.gw),
        grid=(lay.groups, bsz, lay.t_p // lay.gate_tile),
        in_specs=[tile] * n_tiles + [col] * 2,
        out_specs=[tile if len(s.shape) == 3 else col for s in out_shape],
        out_shape=out_shape,
        compiler_params=_params(), interpret=_interpret())


@_shared_pass("lay", "eps")
def _gate_fwd(y, x, zg, d_rep, w, lay: _Layout, eps):
    return _gate_call(_gate_kernel, lay, eps, y.shape[0], 3,
                      [jax.ShapeDtypeStruct(y.shape, y.dtype)])(
                          y, x, zg, d_rep, w)[0]


@_shared_pass("lay", "eps")
def _gate_bwd(g, y, x, zg, d_rep, w, lay: _Layout, eps):
    col = jax.ShapeDtypeStruct((lay.wz, 1), F32)
    return _gate_call(_gate_bwd_kernel, lay, eps, y.shape[0], 4,
                      [jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for a in (y, x, zg)] + [col, col])(
                           g, y, x, zg, d_rep, w)


def _gate_operands(y, x, zg, D, weight, groups):
    d_inner, t = y.shape[1:]
    lay = _layout(t, d_inner, 0, 0, groups)
    d_rep = jnp.repeat(D.astype(F32), d_inner // D.shape[0])[None, :, None]
    return (lay, _tile_x(y, lay), _tile_x(x, lay),
            _pad_groups(d_rep, groups, lay.gwp)[0],
            _pad_groups(weight.astype(F32)[None, :, None], groups,
                        lay.gwp)[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gate_norm(y, x, zg, D, weight, eps: float, groups: int):
    """``GroupRMSNorm((y + D x) silu(z); weight, eps)`` [B, d_inner, T] in
    ``y``'s dtype, ``z`` the first channels behind `_conv_silu`'s handle
    ``zg``, ``x`` its second ``x``.

    The other half of the pair: the cotangent it returns for ``zg`` holds
    ``d z`` in the ``z`` channels and **nothing defined elsewhere**;
    `_conv_silu`'s backward, its one reader, completes it."""
    return _gate_norm_f(y, x, zg, D, weight, eps, groups)[0]


def _gate_norm_f(y, x, zg, D, weight, eps, groups):
    _count("gate_norm", "fwd")
    lay, yp, xp, d_rep, w = _gate_operands(y, x, zg, D, weight, groups)
    out = _gate_fwd(yp, xp, zg, d_rep, w, lay, float(eps))
    return _untile_x(out, lay), (y, x, zg, D, weight)


def _gate_norm_b(eps, groups, res, g):
    _count("gate_norm", "bwd")
    y, x, zg, D, weight = res
    lay, yp, xp, d_rep, w = _gate_operands(y, x, zg, D, weight, groups)
    dy, dx, dzg, dd, dw = _gate_bwd(_tile_x(g, lay), yp, xp, zg, d_rep, w,
                                    lay, float(eps))
    dd = _cut_groups(dd[None], groups, lay.gw).reshape(D.shape[0], -1).sum(-1)
    return (_untile_x(dy, lay), _untile_x(dx, lay), dzg, dd.astype(D.dtype),
            _cut_groups(dw[None], groups, lay.gw)[0, :, 0].astype(
                weight.dtype))


_gate_norm.defvjp(_gate_norm_f, _gate_norm_b)


# -- the chain --------------------------------------------------------------------

def _steps_since_start(segment_ids, most: int):
    """``[B, 1, T]`` int32: how many steps before step ``t`` are of its
    document, clipped to ``most`` (ids non-decreasing along a row, so
    ``d[t] = d[t - j]`` says that all between are the document's too)."""
    d = segment_ids
    since = jnp.zeros(d.shape, jnp.int32)
    for j in range(1, most + 1):
        same = jnp.pad(d[:, j:] == d[:, :-j], [(0, 0), (j, 0)])
        since = since + same.astype(jnp.int32)
    return since[:, None, :]


def mamba_chain(zxbcdt, conv_w, conv_b, D, weight, eps: float, groups: int,
                scan, segment_ids=None):
    """What a Mamba-2 block does between its two projections, [B, d_inner,
    T] in ``zxbcdt``'s dtype::

        x, B, C = silu(conv(x | B | C channels; conv_w, conv_b))
        y = scan(x, B, C, dt)
        out = GroupRMSNorm((y + D x) silu(z); weight, eps)

    ``zxbcdt`` [B, 2 d_inner + 2 n + h, T] is the input projection's
    output, ``[z | x B C | dt]`` along the channels, time minor. The conv
    is causal and depthwise along T (``conv_w`` [K, d_inner + 2 n], tap
    ``K - 1`` on the current step; ``conv_b``); with ``segment_ids`` ([B,
    T] int32, non-decreasing along a row: packed documents) a tap counts
    only where its step is of the current step's document, and the caller's
    ``scan`` has to reset its state there itself; ``D`` [heads] weighs head
    ``i``'s channels ``[i P, (i + 1) P)`` of ``x``; the norm runs over
    ``groups`` equal parts of the channels (``weight`` [d_inner]). Conv,
    ``D``, ``weight`` and every statistic are float32; ``x``, ``B``, ``C``
    and the result are rounded to ``zxbcdt``'s dtype, the gated value is
    not rounded before its norm.

    ``scan(x, B, C, dt) -> y`` is the caller's, differentiated by jax:
    ``x`` [B, d_inner, T], ``B`` and ``C`` [B, n, T], ``dt`` [B, h, T] the
    last ``h`` channels of ``zxbcdt`` as they are, ``y`` like ``x``. It
    may read any of its operands any number of times, or not at all."""
    since = (None if segment_ids is None else
             _steps_since_start(segment_ids, conv_w.shape[0] - 1))
    x, x_again, bm, cm, dt, zg = _conv_silu(zxbcdt, conv_w, conv_b, since,
                                            weight.shape[0], groups)
    return _gate_norm(scan(x, bm, cm, dt), x_again, zg, D, weight, eps,
                      groups)
