"""``tpch``: TPC-H at ``scale_factor``, every table generated in HBM
from the seed and block-distributed over the cell's chips."""
import gc

from benchmarks.harness import tpch_data


class Deployment:
    def __init__(self, config, ctx, seed: int):
        self.scale = float(config["scale_factor"])
        self.ctx, self.seed = ctx, seed
        cols, counts = tpch_data.generate(self.scale, seed, ctx.sharding())
        self.tables = tpch_data.device_tables(ctx, cols, counts, self.scale)

    def facts(self):
        return {}

    def free(self) -> None:
        self.tables = None
        gc.collect()

    def host_data(self, columns):
        """{table: frame} of ``columns`` ({table: [column, ...]}),
        regenerated from the seed."""
        cols, counts = tpch_data.generate(self.scale, self.seed,
                                          self.ctx.sharding())
        try:
            return tpch_data.host_frames(cols, counts, self.scale, columns)
        finally:
            del cols
            gc.collect()
