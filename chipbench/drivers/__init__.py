"""One driver per entry point of the system under test.

A driver module defines `Driver(run)`, where `run` is the harness's
`Run` (configuration, traffic, cell, seed, devices).  A driver has

* `units_per_step`, `rounds_per_step`: what one timed call completes,
  in the unit of the cell's rate (rounds or tokens), and in rounds;
* `flops_per_step`: the operations one call requires (counts/);
* `step()`: one call of the timed path, blocked on its outputs;
* `params()`: the current parameters, copied to the host;
* `alphas()`: the clients' FedNCV alphas (the method's state), copied to
  the host;
* `release()`: frees the program's state before the reference runs;
* `reference(n_steps, dtype)`: the plain reference (reference/) run from
  the same seed through the same inputs as the first n_steps calls,
  returning host parameters after each step and leaving the alphas after
  the first in `ref_alphas`, with `ref_loss(params)` on held-out rows.
"""
