"""Plain reference of one 2D3V PIC step, written from the physics alone.

It imports nothing of the program.  The semantics it follows, step by step:

1. Gather ``E, B`` at every alive particle from the Yee-staggered grid with
   order-3 B-spline weights (periodic index wrap).  Staggering in cells,
   ``(z, x)``: ``Ex (0, 1/2)``, ``Ey (0, 0)``, ``Ez (1/2, 0)``,
   ``Bx (1/2, 0)``, ``By (1/2, 1/2)``, ``Bz (0, 1/2)``.
2. Relativistic Boris push over ``dt`` (momentum ``u = gamma v``, ``c = 1``).
3. Move ``z += dt uz / gamma``, ``x += dt ux / gamma``; a particle that
   leaves ``[0, lz) x [0, lx)`` is absorbed.
4. Deposit ``J = q w u / (gamma dz dx)`` of the moved particles with the
   same weights (direct deposition): ``Jx (0, 1/2)``, ``Jy (0, 0)``,
   ``Jz (1/2, 0)``.
5. Leapfrog FDTD with periodic differences: ``B`` half step, ``E`` full
   step with ``J``, ``B`` half step; then the laser soft source on its row
   (``Ex += s``, ``By -= s``) and a multiplicative sponge on all six
   components.

Particle<->grid transfer is a dense matrix product: each particle's weights
along one axis form a row of an ``(n_particles, n_cells_axis)`` matrix, so
a deposit is ``Wz^T (v * Wx)`` and a gather is ``rowsum((Wz F) * Wx)``.
That keeps the reference on the matrix unit and free of scatters, at the
cost of work in proportion to particles times cells.  Particles go through
in blocks so that the matrices fit.

``precision`` is ``"highest"`` (float32 products, as the configuration
states), ``"bfloat16"`` (operands rounded to bfloat16, float32
accumulation, on every backend) or ``"high"`` (``jax.lax.Precision.HIGH``:
three bfloat16 passes on a TPU, float32 on a CPU).  ``"bfloat16"`` is the
control: the nearest precision below the configuration's float32.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from problems import PARTICLE_KEYS, Domain, Laser

#: rows of particles per block of the dense transfer matrices
BLOCK = 8192


def _mm(a, b, precision: str):
    if precision == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
    if precision == "bfloat16":
        return jnp.dot(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32
        )
    raise ValueError(f"unknown precision {precision!r}")


def _b3(d):
    ax = jnp.abs(d)
    inner = 2.0 / 3.0 - ax * ax + 0.5 * ax * ax * ax
    outer = (2.0 - ax) * (2.0 - ax) * (2.0 - ax) / 6.0
    return jnp.where(ax <= 1.0, inner, jnp.where(ax <= 2.0, outer, 0.0))


def weights_matrix(s, n: int, first=0):
    """``(len(s), n)`` order-3 weights of positions ``s`` (in cells, in a
    frame whose cell 0 is global cell ``first``) on ``n`` grid points,
    indices wrapped periodically."""
    fl = jnp.floor(s)
    frac = s - fl
    i0 = fl.astype(jnp.int32) - 1 + first
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    out = jnp.zeros((s.shape[0], n), jnp.float32)
    for k, d in enumerate((frac + 1.0, frac, 1.0 - frac, 2.0 - frac)):
        hit = cols == jnp.mod(i0 + k, n)[:, None]
        out = out + jnp.where(hit, _b3(d)[:, None], 0.0)
    return out


def _weights(sz, sx, cz, cx, dom: Domain):
    """The four distinct weight matrices (``z`` and ``x`` at staggerings 0
    and 1/2) of box-local positions ``sz, sx`` whose frames start at global
    cells ``cz, cx``."""
    return (
        weights_matrix(sz, dom.nz, cz),
        weights_matrix(sz - 0.5, dom.nz, cz),
        weights_matrix(sx, dom.nx, cx),
        weights_matrix(sx - 0.5, dom.nx, cx),
    )


def _gather(f, wz, wx, precision):
    return jnp.sum(_mm(wz, f, precision) * wx, axis=1)


def _deposit(v, wz, wx, precision):
    return _mm(wz.T, v[:, None] * wx, precision)


def boris(ux, uy, uz, eb, qmdt2):
    ex, ey, ez, bx, by, bz = eb
    umx, umy, umz = ux + qmdt2 * ex, uy + qmdt2 * ey, uz + qmdt2 * ez
    g = jnp.sqrt(1.0 + umx * umx + umy * umy + umz * umz)
    tx, ty, tz = qmdt2 / g * bx, qmdt2 / g * by, qmdt2 / g * bz
    t2 = tx * tx + ty * ty + tz * tz
    upx = umx + (umy * tz - umz * ty)
    upy = umy + (umz * tx - umx * tz)
    upz = umz + (umx * ty - umy * tx)
    s = 2.0 / (1.0 + t2)
    return (
        umx + s * (upy * tz - upz * ty) + qmdt2 * ex,
        umy + s * (upz * tx - upx * tz) + qmdt2 * ey,
        umz + s * (upx * ty - upy * tx) + qmdt2 * ez,
    )


def box_frames(dom: Domain, guard: int):
    """Per box row (column): the first global cell of its guarded frame,
    and that cell's position as float32."""
    nbz, nbx = dom.nz // dom.box, dom.nx // dom.box
    cz = np.arange(nbz) * dom.box - guard
    cx = np.arange(nbx) * dom.box - guard
    return cz, (cz * dom.dz).astype(np.float32), cx, (cx * dom.dx).astype(np.float32)


def _box_of(z, x, dom: Domain):
    nbz, nbx = dom.nz // dom.box, dom.nx // dom.box
    bz = jnp.clip((z / (dom.dz * dom.box)).astype(jnp.int32), 0, nbz - 1)
    bx = jnp.clip((x / (dom.dx * dom.box)).astype(jnp.int32), 0, nbx - 1)
    return bz, bx


def _particle_block(f6, blk, q, m, dom: Domain, guard: int, precision):
    """Gather, push, move and deposit one block; returns the block's new
    state and its ``(3, nz, nx)`` current.

    Positions are taken relative to the guarded frame of the box that
    holds the particle at the step's start (``guard`` cells below the
    box's lower corner), in cells, as box-decomposed PIC codes do: the
    float32 resolution of a position is then that of a box, not of the
    whole domain.
    """
    z, x, ux, uy, uz, w, alive = blk
    fcz, foz, fcx, fox = (jnp.asarray(a) for a in box_frames(dom, guard))
    bz, bx = _box_of(z, x, dom)
    cz, cx, oz, ox = fcz[bz], fcx[bx], foz[bz], fox[bx]
    sz, sx = (z - oz) / dom.dz, (x - ox) / dom.dx
    wz0, wzh, wx0, wxh = _weights(sz, sx, cz, cx, dom)
    ex, ey, ez, bxf, byf, bzf = f6
    eb = (
        _gather(ex, wz0, wxh, precision),
        _gather(ey, wz0, wx0, precision),
        _gather(ez, wzh, wx0, precision),
        _gather(bxf, wzh, wx0, precision),
        _gather(byf, wzh, wxh, precision),
        _gather(bzf, wz0, wxh, precision),
    )
    dt = dom.dt
    qmdt2 = (jnp.float32(q) / jnp.float32(m)) * (0.5 * dt)
    nux, nuy, nuz = boris(ux, uy, uz, eb, qmdt2)
    g = jnp.sqrt(1.0 + nux * nux + nuy * nuy + nuz * nuz)
    sz_n = sz + dt / dom.dz * nuz / g
    sx_n = sx + dt / dom.dx * nux / g
    nz_, nx_ = sz_n * dom.dz + oz, sx_n * dom.dx + ox
    inside = (nz_ >= 0.0) & (nz_ < dom.lz) & (nx_ >= 0.0) & (nx_ < dom.lx)
    live = alive & inside
    coef = jnp.where(live, jnp.float32(q) * w * (1.0 / (dom.dz * dom.dx)), 0.0) / g
    wz0, wzh, wx0, wxh = _weights(sz_n, sx_n, cz, cx, dom)
    j = jnp.stack(
        [
            _deposit(coef * nux, wz0, wxh, precision),
            _deposit(coef * nuy, wz0, wx0, precision),
            _deposit(coef * nuz, wzh, wx0, precision),
        ]
    )
    keep = lambda new, old: jnp.where(alive, new, old)
    out = (keep(nz_, z), keep(nx_, x), keep(nux, ux), keep(nuy, uy), keep(nuz, uz), w, live)
    return out, j


def sponge(dom: Domain, width: int, strength: float):
    iz, ix = np.arange(dom.nz), np.arange(dom.nx)
    ez = np.minimum(iz, dom.nz - 1 - iz)
    ex = np.minimum(ix, dom.nx - 1 - ix)
    dist = np.minimum(ez[:, None], ex[None, :]).astype(np.float32)
    ramp = np.clip(dist / width, 0.0, 1.0)
    return (1.0 - strength * (1.0 - ramp) ** 2).astype(np.float32)


def laser_profile(dom: Domain, laser: Laser | None) -> np.ndarray:
    prof = np.zeros((dom.nz, dom.nx), np.float32)
    if laser is not None:
        x = (np.arange(dom.nx) + 0.5) * dom.dx
        prof[laser.row] = np.exp(-((x - laser.x_center) ** 2) / laser.waist**2)
    return prof


def _field_step(f6, j, t, dom: Domain, laser: Laser | None, sponge_m, profile):
    ex, ey, ez, bx, by, bz = f6
    dt, dz, dx = dom.dt, dom.dz, dom.dx
    fz = lambda a: (jnp.roll(a, -1, 0) - a) / dz
    bz_ = lambda a: (a - jnp.roll(a, 1, 0)) / dz
    fx = lambda a: (jnp.roll(a, -1, 1) - a) / dx
    bx_ = lambda a: (a - jnp.roll(a, 1, 1)) / dx

    def half_b(ex, ey, ez, bx, by, bz):
        h = 0.5 * dt
        return bx + h * fz(ey), by - h * (fz(ex) - fx(ez)), bz - h * fx(ey)

    bx, by, bz = half_b(ex, ey, ez, bx, by, bz)
    ex = ex + dt * (-bz_(by) - j[0])
    ey = ey + dt * (bz_(bx) - bx_(bz) - j[1])
    ez = ez + dt * (bx_(by) - j[2])
    bx, by, bz = half_b(ex, ey, ez, bx, by, bz)
    if laser is not None:
        env = jnp.exp(-(((t - laser.t_peak) / laser.duration) ** 2))
        s = laser.a0 * laser.omega0 * env * jnp.sin(laser.omega0 * t) * laser.omega0 * dt
        ex, by = ex + s * profile, by - s * profile
    return jnp.stack([ex, ey, ez, bx, by, bz]) * sponge_m


def _blocks(a, n_blk):
    return a.reshape(n_blk, -1)


def pad_species(arrays: Dict[str, np.ndarray], block: int) -> Tuple:
    """Host arrays -> a tuple of ``(n_pad,)`` arrays plus ``alive``, padded
    with absorbed particles to whole blocks."""
    n = len(arrays["z"])
    n_pad = max(1, -(-n // block)) * block
    out = []
    for k in PARTICLE_KEYS:
        a = np.zeros(n_pad, np.float32)
        a[:n] = arrays[k]
        out.append(a)
    alive = np.zeros(n_pad, bool)
    alive[:n] = arrays.get("alive", np.ones(n, bool))
    return tuple(out) + (alive,)


@partial(jax.jit, static_argnames=("dom", "laser", "qm", "n_steps", "precision", "block", "sponge_spec", "guard"))
def advance(f6, species, t0, *, dom, laser, qm, n_steps, precision, block, sponge_spec, guard):
    """``n_steps`` reference steps from fields ``f6`` (6, nz, nx) and
    ``species`` (one padded tuple per species).  Returns the new fields,
    species, and per step the per-species alive count of each box at the
    step's start: ``(n_steps, n_species, n_boxes)``."""
    sponge_m = jnp.asarray(sponge(dom, *sponge_spec))
    profile = jnp.asarray(laser_profile(dom, laser))

    def step(carry, i):
        f6, species = carry
        t = t0 + i * dom.dt
        j = jnp.zeros((3, dom.nz, dom.nx), jnp.float32)
        new_species, counts = [], []
        for sp, (q, m) in zip(species, qm):
            counts.append(box_counts(sp[0], sp[1], sp[6], dom))
            n_blk = sp[0].shape[0] // block
            blocks = tuple(_blocks(a, n_blk) for a in sp)

            def body(jacc, blk, q=q, m=m):
                out, jb = _particle_block(f6, blk, q, m, dom, guard, precision)
                return jacc + jb, out

            j, out = jax.lax.scan(body, j, blocks)
            new_species.append(tuple(a.reshape(-1) for a in out))
        f6 = _field_step(f6, j, t, dom, laser, sponge_m, profile)
        return (f6, tuple(new_species)), jnp.stack(counts)

    (f6, species), counts = jax.lax.scan(
        step, (f6, tuple(species)), jnp.arange(n_steps, dtype=jnp.float32)
    )
    return f6, species, counts


def box_counts(z, x, alive, dom: Domain):
    """Alive particles per box, ``(n_boxes,)`` int32."""
    nbz, nbx = dom.nz // dom.box, dom.nx // dom.box
    bz = jnp.clip((z / (dom.dz * dom.box)).astype(jnp.int32), 0, nbz - 1)
    bx = jnp.clip((x / (dom.dx * dom.box)).astype(jnp.int32), 0, nbx - 1)
    ids = jnp.where(alive, bz * nbx + bx, nbz * nbx)
    return jnp.zeros(nbz * nbx + 1, jnp.int32).at[ids].add(1)[:-1]


@partial(jax.jit, static_argnames=("dom", "block"))
def moments(sp, *, dom, block):
    """``(4, nz, nx)`` grid moments ``w * (1, ux, uy, uz)`` of one padded
    species at order-3 weights on unstaggered points (full float32)."""
    n_blk = sp[0].shape[0] // block
    blocks = tuple(_blocks(a, n_blk) for a in sp)

    def body(acc, blk):
        z, x, ux, uy, uz, w, alive = blk
        wz = weights_matrix(z / dom.dz, dom.nz)
        wx = weights_matrix(x / dom.dx, dom.nx)
        ww = jnp.where(alive, w, 0.0)
        return acc + jnp.stack(
            [_deposit(ww * v, wz, wx, "highest") for v in (1.0, ux, uy, uz)]
        ), None

    acc, _ = jax.lax.scan(body, jnp.zeros((4, dom.nz, dom.nx), jnp.float32), blocks)
    return acc


def run(
    dom: Domain,
    laser: Laser | None,
    species: List[Tuple[float, float, Dict[str, np.ndarray]]],
    n_steps: int,
    *,
    precision: str = "highest",
    sponge_spec: Tuple[int, float] = (8, 0.2),
    guard: int = 4,
    block: int = BLOCK,
):
    """Advance host initial state ``n_steps`` steps from zero fields.

    Returns ``(fields (6, nz, nx), [padded species tuple], counts)`` as
    device arrays; ``counts`` is ``(n_steps, n_species, n_boxes)``.
    """
    f6 = jnp.zeros((6, dom.nz, dom.nx), jnp.float32)
    padded = tuple(tuple(jnp.asarray(a) for a in pad_species(arr, block)) for _, _, arr in species)
    qm = tuple((float(q), float(m)) for q, m, _ in species)
    return advance(
        f6, padded, jnp.float32(0.0), dom=dom, laser=laser, qm=qm, n_steps=n_steps,
        precision=precision, block=block, sponge_spec=tuple(sponge_spec), guard=guard,
    )
