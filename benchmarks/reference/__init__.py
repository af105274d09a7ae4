"""Plain references of the benchmark's configurations: jax.numpy only, no
import from the program under test (`fedml_tpu`), nothing the program made."""
