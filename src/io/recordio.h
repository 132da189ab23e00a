/*!
 * \file recordio.h
 * \brief dmlc-compatible RecordIO: the splittable binary record format
 *  the reference's data pipeline is built on (external dmlc-core dep,
 *  used at /root/reference/src/io/iter_image_recordio-inl.hpp:218 and
 *  tools/im2rec.cc). Re-implemented natively for the TPU framework so
 *  .rec archives interchange with reference-packed data.
 *
 * Format (public dmlc spec): each record is
 *   [kMagic:u32][lrec:u32][payload][pad to 4B]
 * where lrec encodes cflag (upper 3 bits) and length (lower 29 bits).
 * Payloads containing the magic word at aligned positions are split
 * into chunks (cflag 0=whole, 1=start, 2=middle, 3=end); readers rejoin
 * chunks re-inserting the magic word. This makes archives seekable:
 * a reader can start at any byte offset and scan to the next record
 * boundary — the basis of InputSplit-style distributed sharding.
 *
 * Image records (image_recordio.h:12-73 parity): payload =
 *   [flag:u32][label:f32][image_id:u64[2]][jpeg bytes]
 *
 * Exposes a C ABI for the Python (ctypes) binding.
 */
#ifndef CXXNET_TPU_IO_RECORDIO_H_
#define CXXNET_TPU_IO_RECORDIO_H_

#include <cstdint>
#include <cstdio>
#include <string>
#include <vector>

namespace cxxnet_tpu {

static const uint32_t kRecordMagic = 0xced7230a;

class RecordIOWriter {
 public:
  explicit RecordIOWriter(const char *path);
  ~RecordIOWriter();
  bool is_open() const { return fp_ != nullptr; }
  void WriteRecord(const void *buf, size_t size);
  void Close();
  /*! \brief true after any short write (e.g. disk full) */
  bool HasError() const { return fail_; }

 private:
  void WriteChunk(const uint32_t *data, size_t nword, uint32_t cflag);
  void Put(const void *data, size_t nmemb);
  FILE *fp_;
  bool fail_ = false;
};

class RecordIOReader {
 public:
  /*!
   * \brief open [part_index, num_parts) byte-range shard of the file;
   *  the reader owning the byte at which a record starts reads it whole
   *  (InputSplit semantics for distributed data sharding,
   *   iter_image_recordio-inl.hpp:183-185)
   */
  RecordIOReader(const char *path, int part_index, int num_parts);
  ~RecordIOReader();
  bool is_open() const { return fp_ != nullptr; }
  /*! \brief read next record into out; false at shard end */
  bool NextRecord(std::string *out);
  void Reset();

 private:
  bool ReadWord(uint32_t *w);
  FILE *fp_;
  uint64_t begin_, end_;   // byte range of this shard
  uint64_t pos_;
};

}  // namespace cxxnet_tpu

extern "C" {
/* C ABI for ctypes */
void *CXNRecordIOWriterCreate(const char *path);
/* returns 0 on success, -1 after a failed write (disk full etc.) */
int CXNRecordIOWriterAppend(void *handle, const char *data,
                            uint64_t size);
void CXNRecordIOWriterFree(void *handle);

void *CXNRecordIOReaderCreate(const char *path, int part_index,
                              int num_parts);
/* returns pointer to internal buffer valid until next call; len=0 at
 * end of shard */
const char *CXNRecordIOReaderNext(void *handle, uint64_t *size);
/* up to n records in one call: *data points at their bytes laid end to
 * end, *sizes at their lengths, both valid until the next call; returns
 * how many were read (fewer than n only at the end of the shard) */
uint64_t CXNRecordIOReaderNextN(void *handle, uint64_t n,
                                const char **data,
                                const uint64_t **sizes);
void CXNRecordIOReaderReset(void *handle);
void CXNRecordIOReaderFree(void *handle);
}

#endif  // CXXNET_TPU_IO_RECORDIO_H_
