"""``join``: two join sides of ``rows_per_chip`` rows on every chip,
generated in HBM from the seed."""
import gc

from benchmarks.harness import join_data


class Deployment:
    def __init__(self, config, ctx, seed: int):
        self.rows_per_chip = int(config["rows_per_chip"])
        self.chips = ctx.get_world_size()
        self.ctx, self.seed = ctx, seed
        left, right, self.cap = join_data.generate(
            self.rows_per_chip, self.chips, seed, ctx.sharding())
        self.tables = join_data.device_tables(ctx, left, right, self.cap,
                                              self.rows_per_chip)

    def facts(self):
        n = self.rows_per_chip * self.chips
        return {"rows_left": n, "rows_right": n,
                "bytes_per_row_in": 16, "bytes_per_row_out": 32}

    def free(self) -> None:
        self.tables = None
        gc.collect()

    def host_data(self, columns=None):
        """(left frame, right frame), regenerated from the seed."""
        left, right, cap = join_data.generate(
            self.rows_per_chip, self.chips, self.seed, self.ctx.sharding())
        try:
            return tuple(join_data.host_side(c, cap, self.rows_per_chip,
                                             self.chips)
                         for c in (left, right))
        finally:
            del left, right
            gc.collect()
