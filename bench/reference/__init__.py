"""Plain references: straightforward jnp at "highest" precision, written
from the configuration files alone.  Nothing here imports the program."""
