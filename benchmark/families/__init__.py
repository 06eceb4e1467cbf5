"""Model families: how a configuration file (the source's own keys) becomes
the program's configuration, its plain reference and its operation counts.
A configuration's ``family`` key names the module."""

import jax.numpy as jnp

#: ``activation_dtype`` / ``weight_dtype`` in a configuration file
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
