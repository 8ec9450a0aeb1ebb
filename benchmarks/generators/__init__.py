"""Data generators, one module a name; a configuration's file names its
generator and the parameters it is called with (``generate(params, seed)``)."""
