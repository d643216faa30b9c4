"""The least work of one step (one tree) of a level-wise random forest,
and of one call of the histogram kernel under the forest's batching, from
the configuration alone.

A tree of a forest looks at its own k columns (`feature_subset_cols`) and
no others, so a histogram pass over n rows makes one add each into the
gradient and the hessian histogram for k columns: 2*n*k operations. It
reads, for each row, 12 bytes of the TREE's row state (gradient, hessian,
node id) and the row's bins; the bins belong to no tree, and one read of a
bin (one byte is what up to 256 bins need) can serve every tree that is
grown beside it. The least is therefore a forest whose T trees all share
every read of the table: n*(C/T + 12) bytes a tree a pass, C all columns
(with T = 10 subsets of 18 of 28 the union is every column).

Passes a tree, as the `gbt` family counts them: depth D needs the
histograms of levels 0..D-1, the root over every row, each deeper level
over the smaller child of every split (the sibling comes by subtraction,
so at most half the rows): 1 + (D-1)/2. One more pass of 12 bytes a row
draws the tree's instance weights and makes its gradients.

One call of the kernel covers a lockstep group of the program
(`lockstep_group_trees`, G trees; the configuration records what the
program's own sizing gives at these rows): ONE read of the bins for the
group and 12 bytes of row state a tree of the group, 2*n*k operations a
tree. A forest of T trees in groups of G makes ceil(T/G) such calls a
level, the last of them smaller where G does not divide T, and the
reader takes one least time for every event it counts: the mean over a
level's calls, T trees' row state and ceil(T/G) reads of the table.
"""

ROW_STATE_BYTES = 12


def step_work(config):
    rows, cols = config["train_rows"], config["input_dim"]
    passes = 1 + (config["max_depth"] - 1) / 2
    return {"flops": passes * 2 * rows * config["feature_subset_cols"],
            "bytes": passes * rows * (cols / config["n_trees"]
                                      + ROW_STATE_BYTES)
            + ROW_STATE_BYTES * rows}


def kernel_call_work(config, chips: int):
    """The mean call of the histogram kernel on one chip: a pass over the
    rows that chip holds for one lockstep group."""
    rows = config["train_rows"] // chips
    trees = config["n_trees"]
    calls = -(-trees // config["lockstep_group_trees"])
    return {"flops": 2 * rows * config["feature_subset_cols"] * trees / calls,
            "bytes": rows * (config["input_dim"]
                             + ROW_STATE_BYTES * trees / calls)}
