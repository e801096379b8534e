"""How much of what the block-sparse kernels visit the tokens selected:
the (token, block) pairs the tokens selected / the pairs the kernels
visited (a tile of queries visits the union of its tokens' blocks, each
for all of them), summed over the sparse layers, at the window's last
step (the program's step counters, which the driver hands on as
`sparse_visits`). 100 is a kernel that visits nothing a token did not
select. Nothing to read where the program has no such counters."""


def read(run):
  found = run["window"].get("sparse_visits")
  if not found or not found["visited"]:
    return None
  return 100.0 * found["selected"] / found["visited"]
