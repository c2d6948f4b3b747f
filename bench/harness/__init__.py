"""The benchmark's own machinery: loading cells by name, seeds, window
arithmetic, the LM and KAN cell runners, and the trace reduction."""
