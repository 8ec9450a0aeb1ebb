"""One general driver for each kind of traffic; a traffic file names its
driver and gives its parameters."""
