"""Plain references: each model's forward (and loss) in straightforward
``jax.numpy`` at float32, ``highest`` matmul precision, with no kernel,
cache or batching, and the DiLoCo training algorithm around it. They
import nothing of the program; they read the parameter layout by name
and are given the benchmark's own weights and inputs.

``precision="fp8"`` rounds every matmul operand and every value the
model stores between operations (residual stream, logits) to float8
(e4m3, one scale per tensor): the control, one step below the bfloat16
that the configurations state."""
