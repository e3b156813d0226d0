"""Initial plasma state of a configuration, made on the device from a seed.

A configuration file (``configs/<name>.json``) states the deployment: the
cells one chip holds, the box size, particles per cell (``ppc``), the ion
mass ratio, the electron thermal momentum, a density profile and, for the
laser-ion run, the antenna.  ``chips`` chips hold ``chips`` times the
cells (the domain side grows by ``sqrt(chips)``); the profile and the laser
scale with the domain, so every chip keeps its share of the deployment.

Every occupied cell holds exactly ``ppc`` markers per species at uniform
random offsets inside the cell (``CELL_MARGIN`` from its faces), so the
particle count and every box's population are the same for every seed:
the seed moves particles within their cells and draws their momenta,
nothing else.  Electrons and ions are
placed independently, as the paper's setup samples them.

This module imports nothing of the program: :func:`make_state` returns
plain arrays, which the harness hands to the program and to the reference
alike.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: particle arrays of one species, in this order
PARTICLE_KEYS = ("z", "x", "ux", "uy", "uz", "w")


@dataclass(frozen=True)
class Domain:
    """Grid geometry of one run: ``nz x nx`` cells of ``dz x dx``, boxes of
    ``box x box`` cells."""
    nz: int
    nx: int
    dz: float
    dx: float
    box: int
    cfl: float = 0.999

    @property
    def lz(self) -> float:
        return self.nz * self.dz

    @property
    def lx(self) -> float:
        return self.nx * self.dx

    @property
    def dt(self) -> float:
        return self.cfl / math.sqrt(1.0 / self.dz**2 + 1.0 / self.dx**2)


@dataclass(frozen=True)
class Laser:
    """Soft-source antenna on one grid row: ``Ex += s(t) * profile`` and
    ``By -= s(t) * profile`` after each field update."""
    a0: float
    omega0: float
    waist: float
    duration: float
    t_peak: float
    row: int
    x_center: float


def domain_of(config: Dict, chips: int) -> Domain:
    side = math.isqrt(chips)
    if side * side != chips:
        raise ValueError(f"{chips} chips do not tile a square domain")
    nz, nx = (int(c) * side for c in config["cells_per_chip"])
    h = float(config["cell_size"])
    return Domain(nz=nz, nx=nx, dz=h, dx=h, box=int(config["box_cells"]))


def density_map(config: Dict, dom: Domain) -> np.ndarray:
    """Per-cell density (1 = the reference plasma density), ``(nz, nx)``."""
    prof = config["profile"]
    kind = prof["kind"]
    if kind == "uniform":
        return np.ones((dom.nz, dom.nx))
    if kind == "disk":
        zc, xc = prof["center_frac"][0] * dom.lz, prof["center_frac"][1] * dom.lx
        r_core = prof["r_core_frac"] * min(dom.lz, dom.lx)
        r_slope = prof["slope_over_core"] * r_core
        edge = prof["edge_over_core"] * r_core + prof["edge_floor"]
        zg = (np.arange(dom.nz) + 0.5) * dom.dz
        xg = (np.arange(dom.nx) + 0.5) * dom.dx
        rr = np.sqrt((zg[:, None] - zc) ** 2 + (xg[None, :] - xc) ** 2)
        return np.where(
            rr <= r_core,
            1.0,
            np.where(rr <= r_core + r_slope, np.exp(-(rr - r_core) / edge), 0.0),
        )
    raise ValueError(f"unknown density profile {kind!r}")


def occupied_cells(config: Dict, dom: Domain) -> Tuple[np.ndarray, np.ndarray]:
    """``(cells, density)``: the ``(n, 2)`` cell indices that hold markers
    and their densities."""
    dens = density_map(config, dom)
    cells = np.argwhere(dens > float(config["profile"].get("min_density", 0.0)))
    return cells, dens[cells[:, 0], cells[:, 1]]


def laser_of(config: Dict, dom: Domain):
    spec = config.get("laser")
    if spec is None:
        return None
    return Laser(
        a0=float(spec["a0"]),
        omega0=float(spec["omega0"]),
        waist=spec["waist_frac"] * dom.lx,
        duration=spec["duration_per_lz"] * dom.lz,
        t_peak=spec["t_peak_frac"] * dom.lz,
        row=int(spec["z_pos_cells"]),
        x_center=spec["x_center_frac"] * dom.lx,
    )


def species_of(config: Dict):
    """``[(name, charge, mass, thermal_u per axis)]`` for each species."""
    u_e = float(config["electron_thermal_u"])
    return [
        ("electrons", -1.0, 1.0, tuple(u_e * a for a in config["electron_thermal_axes"])),
        ("ions", 1.0, float(config["mass_ratio"]), (0.0, 0.0, 0.0)),
    ]


#: offsets inside a cell keep this far (in cells) from its faces, so that a
#: position rounded to float32 never lands in the next cell, or the next
#: box: every seed then gives every box the same population, and so the
#: same slot shapes and compiled programs
CELL_MARGIN = 1e-3


def _offset(key, n: int):
    u = jax.random.uniform(key, (n,), jnp.float32)
    return CELL_MARGIN + u * (1.0 - 2.0 * CELL_MARGIN)


def seed_key(seed: int):
    """A jax PRNG key from any non-negative whole number (wider than 32
    bits too: the high word is folded in)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_state(config: Dict, chips: int, seed: int):
    """Both species' markers, made on the device in one jitted call.

    Returns ``(domain, laser, species)``; ``species`` is a list of
    ``(charge, mass, {key: (n,) float32 device array})``.
    """
    dom = domain_of(config, chips)
    cells, dens = occupied_cells(config, dom)
    ppc = int(config["ppc"])
    sp = species_of(config)
    cz = np.repeat(cells[:, 0], ppc).astype(np.float32)
    cx = np.repeat(cells[:, 1], ppc).astype(np.float32)
    w = np.repeat(dens * dom.dz * dom.dx / ppc, ppc).astype(np.float32)
    n = len(cz)

    @jax.jit
    def build(key, cz, cx, w):
        out = []
        for k, (_, _, _, th) in zip(jax.random.split(key, len(sp)), sp):
            kz, kx, ku = jax.random.split(k, 3)
            arrs = {
                "z": (cz + _offset(kz, n)) * dom.dz,
                "x": (cx + _offset(kx, n)) * dom.dx,
                "w": w,
            }
            normal = jax.random.normal(ku, (3, n), jnp.float32)
            for i, name in enumerate(("ux", "uy", "uz")):
                arrs[name] = normal[i] * jnp.float32(th[i])
            out.append(arrs)
        return out

    arrays = build(seed_key(seed), cz, cx, w)
    species = [(q, m, a) for (_, q, m, _), a in zip(sp, arrays)]
    return dom, laser_of(config, dom), species
