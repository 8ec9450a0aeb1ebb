"""What a tree *requires*, counted from the trees that were grown: the same
count whatever kernel does the work.  One module a name; a layer metric's
file names the count it is measured against."""
