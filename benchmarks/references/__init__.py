"""Plain references, one module a name.  Nothing here imports the program
or takes a number the program computed: a reference gets the raw arrays the
generator made, the configuration's parameters, and the program's *answers*
(the trees it says it grew, the bin bounds it chose) to hold against them."""
