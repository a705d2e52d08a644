"""Algorithmic work of one implicit-ALS iteration, from the configuration's
shapes alone, whatever layout (dense planes or sparse chunks) or solver
implements it. Per rating and side: the rank-k outer product accumulated into
the row's normal equations (2 k^2) and its right-hand side (2 k); per row: a
Cholesky factorisation and two substitutions (k^3/3 + 2 k^2); V'V once a
side."""


def work(config: dict, traffic: dict) -> dict:
    p = traffic["params"]
    nnz, k = int(p["ratings"]), int(config["rank"])
    rows, cols = int(p["rows"]), int(p["cols"])
    solve_flops = (rows + cols) * (k ** 3 / 3.0 + 2.0 * k * k)
    return {"flops_per_epoch": (2.0 * nnz * (2.0 * k * k + 2.0 * k)
                                + solve_flops
                                + 2.0 * (rows + cols) * k * k),
            # each rating once a side (row, column, value: 12 B) and both
            # factor tables read and written once in float32
            "bytes_per_epoch": 2.0 * 12.0 * nnz + 2.0 * (rows + cols) * k * 4.0,
            "samples_per_epoch": nnz,
            # the solves alone: every system's matrix and right-hand side
            # read and its solution written once in float32
            "solve_flops_per_epoch": solve_flops,
            "solve_bytes_per_epoch": (rows + cols) * (k * k + 2.0 * k) * 4.0}
