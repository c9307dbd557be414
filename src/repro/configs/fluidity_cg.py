"""The paper's own benchmark configuration: Fluidity pressure-solve matrices
on the hybrid (node x core) mesh.  Sizes mirror Sec. 3/4 of the paper:
the Fig. 3 matrix has 13.5M DoF / 371M nnz; Fig. 4 has 52M DoF / 1.46B nnz.

``extruded_mesh_matrix`` matches the DoF but not the density: its rows
couple a surface point's ~6 in-layer neighbours and the layers above and
below, ~9 nnz/row where Fluidity's P1-P1 pressure matrices have ~27.
With seed 0, ``PAPER_SMALL`` gives 13,440,000 rows and 120,520,800 nnz
(8.97 per row) and ``PAPER_LARGE`` 53,760,000 rows and 483,343,200 nnz
(8.99 per row).  CPU-runnable scaled-down versions are provided for
measurement."""
import dataclasses

from repro.configs.base import register


@dataclasses.dataclass(frozen=True)
class CGConfig:
    name: str
    n_surface: int          # 2-D coastline points
    layers: int             # vertical extrusion (workload scaling knob)
    seed: int = 0
    tol: float = 1e-8
    maxiter: int = 10_000   # paper Sec. 3
    mode: str = "balanced"

    @property
    def approx_dof(self) -> int:
        return self.n_surface * self.layers


# paper-scale matrices: PAPER_SMALL (13.44M rows, 120.5M nnz) fits one v5e
# chip (chip_smoke.py runs it cut to 16 layers); PAPER_LARGE (53.76M rows,
# 483.3M nnz) needs the four-chip host
PAPER_SMALL = CGConfig("fig3-13.5M", n_surface=210_000, layers=64)
PAPER_LARGE = CGConfig("fig4-52M", n_surface=210_000, layers=256)
# CPU-measurable versions
BENCH_SMALL = CGConfig("bench-small", n_surface=2_000, layers=16)
BENCH_LARGE = CGConfig("bench-large", n_surface=2_000, layers=64)
