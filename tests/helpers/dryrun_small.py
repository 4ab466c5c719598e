"""Lower+compile train/prefill/decode for reduced archs on a 4x2 mesh of
8 forced host devices -- the same code path as the 512-device dry-run."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import input_specs
from repro.models import Transformer, reduced
from repro.models.config import ShapeConfig

SHAPES = [ShapeConfig("t", 64, 8, "train"),
          ShapeConfig("p", 64, 8, "prefill"),
          ShapeConfig("d", 64, 8, "decode")]


def main():
    mesh = make_mesh((4, 2), ("data", "model"))
    fails = []
    for arch in ARCHS:
        cfg = reduced(get_config(arch))
        for shape in SHAPES:
            try:
                with jax.set_mesh(mesh):
                    cell = input_specs(cfg, shape, mesh)
                    if cell.kind == "train":
                        args = (cell.params, cell.opt, cell.batch)
                    elif cell.kind == "prefill":
                        args = (cell.params, cell.batch)
                    else:
                        args = (cell.params, cell.cache, cell.batch)
                    jax.jit(cell.fn).lower(*args).compile()
                print(f"ok {arch} {shape.kind}")
            except Exception as e:
                fails.append((arch, shape.kind, repr(e)[:300]))
                print(f"FAIL {arch} {shape.kind}: {e!r}"[:400])
    raise SystemExit(1 if fails else 0)


if __name__ == "__main__":
    main()
